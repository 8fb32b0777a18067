"""The serving engine: continuous batching over a paged KV cache.

Replaces the reference's per-model vLLM container (``SURVEY.md`` §2.2, §7
stage 2).  One ``Engine`` owns one model's weights + page pool on a mesh
slice and exposes token-level ``add_request`` / ``step`` — the OpenAI HTTP
surface (``helix_tpu.serving``) sits on top, the multi-model residency
manager (``helix_tpu.engine.residency``) creates/destroys Engines per the
active profile.

Execution model (all shapes static, everything jitted once per bucket):

- **Prefill**: one request per call, prompt padded to a power-of-two bucket;
  flash attention over its own K/V; fresh K/V scattered into the request's
  pages; last-token logits sampled for the first generated token.
- **Decode**: one fused step for all ``max_decode_batch`` slots — forward
  (paged attention over each slot's page table) + KV write + penalty +
  sampling inside a single jit; inactive slots ride along pointed at the
  garbage page.
- **Mixed step**: while a long prompt chunk-prefills, the chunk and every
  active decode slot run in ONE device call per engine step (ragged row
  lengths over the shared page pool) — decode never stalls during
  admission and never pays a second dispatch.
- **Int8 KV** (``EngineConfig.kv_cache_dtype="int8"``): pages store codes
  + per-(slot, head) f32 scales; ~2x the cached tokens per HBM byte, with
  in-register dequant in the paged kernel.
- **Speculative decoding** (``EngineConfig.enable_spec_decode``): the host
  drafts up to ``spec_tokens`` continuation tokens per slot via
  prompt-lookup n-grams (``engine/spec.py`` — no draft model), and ONE
  device call scores all k+1 positions per slot against its ragged paged
  history, accepting the longest prefix the model's own sampling agrees
  with — accepted tokens cost no extra forward pass, and a per-slot
  acceptance EMA degrades the worst case back to the plain fused window.
- Host side keeps plain-Python queues, a page allocator, and per-request
  state; nothing dynamic ever crosses into traced code.
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import functools
import itertools
import logging
import os
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from helix_tpu.engine import ragged as ragged_meta
from helix_tpu.engine.kv_cache import (
    CacheConfig,
    ColdPageError,
    PageAllocator,
    PagedKVCache,
    slot_to_page_offset,
    write_kv,
)
from helix_tpu.engine.ragged import PrefillPlan, bucket_tokens
from helix_tpu.engine.sampling import (
    SamplingParams,
    SamplingState,
    apply_penalties,
    sample,
    split_keys,
)
from helix_tpu.models.common import ModelConfig
from helix_tpu.models.mixers import PAGE_KINDS, STATE_MIXERS
from helix_tpu.models.llama import forward, lm_head
from helix_tpu.obs import trace as obs_trace
from helix_tpu.obs.slo import ANON_TENANT
from helix_tpu.ops.attention import attention as full_attention


class FinishReason(str, enum.Enum):
    STOP = "stop"
    LENGTH = "length"
    ABORT = "abort"


@dataclasses.dataclass
class Request:
    id: str
    prompt_tokens: list
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    stop_token_ids: tuple = ()
    # --- multimodal (Qwen2-VL family) ---
    image_embeds: Optional[object] = None    # [N_img_tokens, E] device array
    image_positions: Optional[list] = None   # indices of image tokens in prompt
    positions3: Optional[object] = None      # np [3, S] mrope position streams
    mrope_delta: int = 0                     # decode-time stream offset
    # mutable state
    output_tokens: list = dataclasses.field(default_factory=list)
    finished: bool = False
    finish_reason: Optional[FinishReason] = None
    slot: Optional[int] = None
    max_len: Optional[int] = None   # page-capacity cap set at admission
    submit_time: float = dataclasses.field(default_factory=time.monotonic)
    admitted_time: Optional[float] = None   # slot claimed (queue wait ends)
    first_token_time: Optional[float] = None
    first_emit_time: Optional[float] = None   # first token left the loop
    # end-to-end trace identity (obs.trace): minted at the OpenAI
    # endpoint, carried through dispatch into engine-level spans; empty
    # string = untraced (span recording is then a no-op)
    trace_id: str = ""
    # tenant identity (obs.slo): auth-resolved at the control plane,
    # adopted from X-Helix-Tenant by the OpenAI surface — feeds the
    # bounded per-tenant accounting and the admission audit trail
    tenant: str = ANON_TENANT
    # priority class (serving/sched.py): "interactive" | "batch";
    # "" lets the engine loop stamp the profile's default at submit
    sched_class: str = ""
    # multi-LoRA adapter id (engine/adapters.py): sanitised at the
    # OpenAI surface from `model@adapter` addressing; "" = base model.
    # The engine resolves it to an HBM pool slot at admission (deferred
    # — never blocking a step — while the adapter is cold) and holds
    # one pool ref until finish
    adapter: str = ""
    cached_tokens: int = 0          # prompt tokens served by prefix cache
    preempt_count: int = 0          # times swapped out (bounds thrash)
    # force full device residency even on a tiered engine: context-cache
    # creation prefills (serving/context_cache.py) must keep every page
    # resident so the prefix cache / filestore can adopt them
    ctx_pin: bool = False
    _page_hashes: Optional[list] = None

    @property
    def num_tokens(self) -> int:
        return len(self.prompt_tokens) + len(self.output_tokens)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_decode_batch: int = 8
    page_size: int = 16
    num_pages: int = 2048
    max_pages_per_seq: int = 128
    max_prefill_len: int = 2048   # chunk size: longer prompts prefill in
    # max_prefill_len-sized chunks appended to the same page table across
    # engine steps, interleaved with decode (vLLM --max-model-len analogue:
    # the true prompt limit is max_model_len / page capacity, not this)
    max_model_len: Optional[int] = None  # None = page capacity
    attn_backend: Optional[str] = None   # None = pallas on TPU, reference on CPU
    eos_token_ids: tuple = ()
    # Decode steps fused into ONE jit call (lax.scan) between host syncs.
    # Steady-state decode then fetches tokens to host once per WINDOW, not
    # once per token — the lever that matters when a host fetch costs
    # more than a decode step (its cost is not measured on the current
    # chip).  vLLM calls the same idea "multi-step scheduling".  The
    # engine drops to single steps while admission/chunked-prefill work
    # is pending and near per-request token caps, so semantics are
    # unchanged; streaming consumers see tokens in bursts of at most
    # this many.
    decode_steps_per_sync: int = 1
    # Adaptive streaming cadence: with at most this many active slots the
    # engine syncs EVERY step so interactive chats stream per-token; the
    # fused window only engages once the batch is big enough that
    # amortising the host round trip beats per-token latency (round-3
    # verdict weak #5 — bursty cadence is the wrong default for chat).
    adaptive_sync_max_streams: int = 2
    # Automatic prefix caching (vLLM APC): full prompt pages are content-
    # hashed and shared across requests — a request whose prompt starts
    # with an already-cached prefix skips prefilling those tokens (the
    # shared-system-prompt TTFT lever).  Pages stay read-only by
    # construction: the shareable prefix is capped at the prompt's FULL
    # pages below its last token, and decode writes only past the prompt.
    enable_prefix_cache: bool = True
    # KV page-pool storage dtype: "auto" stores at the model dtype;
    # "int8" stores codes + per-(slot, head) f32 scales, halving page
    # bytes (CacheConfig.fit_hbm then admits 1.88-1.94x the pages at
    # head_dim 128, see CacheConfig.page_bytes) with dequantization on
    # the score side inside the paged kernel.  vLLM analogue:
    # --kv-cache-dtype fp8/int8.
    kv_cache_dtype: str = "auto"   # auto | bfloat16 | float32 | int8
    # Ragged mixed prefill/decode step: while a long prompt chunk-
    # prefills, pack the chunk AND every active decode slot into ONE
    # device call per engine step (the decode rows walk their ragged page
    # tables in the paged kernel, the chunk attends its gathered history
    # — same pool, same traced program).  Decode keeps emitting a token
    # every engine step during long-prompt admission without paying two
    # serialized dispatches; vLLM v1 calls this a mixed batch.
    enable_mixed_step: bool = True
    # Speculative decoding (engine/spec.py): draft up to spec_tokens
    # continuation tokens per slot on the HOST (prompt-lookup n-grams —
    # no draft model), then score all k+1 positions in ONE device call
    # (a short ragged chunk per slot over its paged history) and accept
    # the longest draft prefix the model agrees with.  Each accepted
    # token is a decode forward pass the request never runs.  Sampling
    # at every verified position draws from the request's own
    # SamplingParams tiers, so the output distribution is exactly the
    # non-speculative one (greedy is bit-identical); a per-slot
    # acceptance EMA turns speculation off for slots whose drafts keep
    # missing, so the worst case degenerates to the existing fused
    # window.  Not supported for mrope (VL) or MoE models (expert
    # capacity is shared across the verify chunk, which would perturb
    # routing vs plain decode) — the engine logs and disables there.
    enable_spec_decode: bool = False
    spec_tokens: int = 4
    # Continuous multi-LoRA serving (engine/adapters.py): >= 2 turns on
    # the batched adapter path — a fixed-capacity stacked HBM pool of
    # LoRA factors (slot 0 reserved for the zero identity adapter) is
    # grafted into the unified ragged step, every device-step row
    # carries its adapter slot in the per-row metadata, and the
    # projections add scale * (x @ A[g]) @ B[g] per token via a batched
    # gather-matmul — so N tenants' adapters serve against ONE resident
    # base model with no per-tenant model copies, no hot-swap compile
    # waves, and no new trace families (the pool shape is compiled once
    # at warmup; loading an adapter later writes values into the same
    # arrays).  0 = off (seed behaviour; `adapter:` profile merging
    # still works as the single-adapter fallback).  Node-level
    # override: HELIX_ADAPTER_POOL_SLOTS.  Unsupported for mrope (VL)
    # models — the single-shot VL prefill does not thread per-token
    # adapter ids.
    adapter_pool_slots: int = 0
    # pool-wide rank capacity: adapters with smaller rank zero-pad
    # (exact — zero rows of A and zero columns of B contribute nothing)
    adapter_rank: int = 16
    # LoRA targets the pool serves (must cover every published
    # adapter's targets; attention-only by default — MoE FFNs are not
    # adaptable, dense FFN targets can be added per profile)
    adapter_targets: tuple = ("wq", "wk", "wv", "wo")
    # Host-RAM KV tier (engine/kv_cache.HostPagePool): byte budget for
    # spilled pages.  >0 turns the tier on: PrefixCache evictions demote
    # page contents to host buffers instead of dying (restored into
    # fresh device pages when a later prompt chains onto the digest —
    # the 10-100x effective-prefix-cache lever for system-prompt-heavy
    # fleets), and Engine.preempt can swap a running slot's private
    # pages + sampling state out and exactly resume it later
    # (preemption-by-swap; the graceful-degradation lever under KV
    # exhaustion).  0 = no host tier (seed behaviour: evictions free,
    # preemption unavailable).  Node-level override:
    # HELIX_KV_HOST_POOL_BYTES.
    host_pool_bytes: int = 0
    # Tiered KV residency for long contexts (ISSUE 20): > 0 turns on
    # streamed chunked attention — a sequence keeps only its last
    # ctx_hot_pages full pages (plus the partially written head page and
    # any shared prefix) resident in the device pool; the cold middle
    # demotes to the host tier page by page as decode/prefill advances,
    # and every device step attends it from staged fixed-size chunks via
    # the ring-attention online-softmax combine.  Context length is then
    # bounded by the PAGE TABLE WIDTH (max_pages_per_seq * page_size),
    # not the physical pool — the million-token-context lever.  Requires
    # host_pool_bytes > 0; greedy and seeded outputs are bit-identical
    # with tiering on vs fully resident.  Node-level override:
    # HELIX_CTX_HOT_PAGES.  0 = off (seed behaviour).
    ctx_hot_pages: int = 0
    # Pages per staged cold chunk: each chunk gathers this many demoted
    # pages from the host tier (checksum-verified per page) into one
    # partial-attention block.  Larger chunks = fewer merge steps and
    # fewer compiled chunk-count buckets, more transient HBM per step.
    ctx_stream_pages: int = 4

    def cache_config(self, dtype: str = "bfloat16") -> CacheConfig:
        kv_dtype = (
            dtype
            if self.kv_cache_dtype in ("auto", None, "")
            else self.kv_cache_dtype
        )
        return CacheConfig(
            num_pages=self.num_pages,
            page_size=self.page_size,
            max_pages_per_seq=self.max_pages_per_seq,
            dtype=kv_dtype,
            state_slots=self.max_decode_batch,
        )


def _bucket(n: int, lo: int, hi: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return min(b, hi) if b <= hi else hi


# ---------------------------------------------------------------------------
# Host-side PRNG key derivation (no device round trips — see _request_key)
# ---------------------------------------------------------------------------

_M64 = (1 << 64) - 1
_SEED_DOMAIN = 0xA076_1D64_78BD_642F  # seeded-request key domain


def _splitmix64(x: int) -> int:
    x = (x + 0x9E37_79B9_7F4A_7C15) & _M64
    z = ((x ^ (x >> 30)) * 0xBF58_476D_1CE4_E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D0_49BB_1331_11EB) & _M64
    return z ^ (z >> 31)


def _host_key(x: int) -> np.ndarray:
    """uint32[2] threefry key data from a 64-bit state."""
    z = _splitmix64(x)
    return np.array([z >> 32, z & 0xFFFF_FFFF], np.uint32)


def _host_split(key: np.ndarray, n: int = 2) -> list:
    """Derive n child keys from a host key, deterministically."""
    base = (int(key[0]) << 32) | int(key[1])
    return [_host_key(base ^ (0xD6E8_FEB8_6659_FD93 * (i + 1))) for i in
            range(n)]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DecodeState:
    """Device-resident per-slot decode state.

    Steady-state decode never uploads anything from the host: last
    tokens, positions, page tables, RNG keys, and the output-token
    histogram (for presence/frequency penalties) all live on device and
    are advanced inside the fused step.  The host re-syncs the state only
    when the slot set changes (admission / completion) via one jitted
    merge (``_rebuild_state``) that preserves the device-evolving
    pieces (last tokens, positions, keys, histograms) of surviving slots:
    the host supplies them for changed slots only, so a rebuild is valid
    while a step is still in flight (its tokens are not on the host yet).
    """

    last_token: jax.Array    # [B] i32
    positions: jax.Array     # [B] i32
    page_tables: jax.Array   # [B, P] i32
    active: jax.Array        # [B] i32
    mrope_delta: jax.Array   # [B] i32
    keys: jax.Array          # [B, 2] u32 — per-slot PRNG keys
    token_counts: jax.Array  # [B, V] i32 — output-token histogram
    adapter_slots: jax.Array  # [B] i32 — multi-LoRA pool slot (0 = none)
    sampling: SamplingState


@functools.partial(jax.jit, donate_argnums=(0,))
def _rebuild_state(
    old: DecodeState, last_token, positions, page_tables, active,
    mrope_delta, new_keys, keep, adapter_slots, sampling,
) -> DecodeState:
    B = last_token.shape[0]
    keepc = keep[:, None] > 0
    # fresh slots start their histogram with the prefill-sampled first
    # token (it is output token #1 for penalty purposes)
    fresh = jnp.zeros_like(old.token_counts)
    fresh = fresh.at[jnp.arange(B), jnp.clip(last_token, 0)].add(
        ((keep == 0) & (active > 0)).astype(fresh.dtype)
    )
    return DecodeState(
        last_token=jnp.where(keep > 0, old.last_token, last_token),
        positions=jnp.where(keep > 0, old.positions, positions),
        page_tables=page_tables,
        active=active,
        mrope_delta=mrope_delta,
        keys=jnp.where(keepc, old.keys, new_keys),
        token_counts=jnp.where(keepc, old.token_counts, fresh),
        adapter_slots=adapter_slots,
        sampling=sampling,
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _override_token_counts(state: DecodeState, slot, counts) -> DecodeState:
    """Replace ONE slot's device-resident output-token histogram — the
    exact-resume path restores the penalty state a preempted request had
    evolved on device (``_rebuild_state``'s fresh-slot histogram only
    seeds the first token, which would skew presence/frequency penalties
    after a swap-in)."""
    return dataclasses.replace(
        state, token_counts=state.token_counts.at[slot].set(counts)
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _patch_first_tokens(state: DecodeState, src, toks) -> DecodeState:
    """Seed fresh slots' device-resident last_token + histogram from a
    still-on-device first-token handle ``toks [R]`` (an admission wave's,
    or a final chunk's): slot ``b`` takes ``toks[src[b]]``, ``src[b]`` -1
    leaves it alone.  ``_rebuild_state`` seeded the slot from the host
    mirror's placeholder 0, so move that histogram count to the real
    token and set last_token: the decode step that follows then
    conditions on the true first token without the host having fetched
    it."""
    has = src >= 0
    tok = toks[jnp.clip(src, 0)]
    rows = jnp.arange(src.shape[0])
    # a slot launched inactive was given no placeholder count to move
    d = (has & (state.active > 0)).astype(state.token_counts.dtype)
    counts = state.token_counts.at[rows, 0].add(-d)
    counts = counts.at[rows, tok].add(d)
    return dataclasses.replace(
        state,
        last_token=jnp.where(has, tok, state.last_token),
        token_counts=counts,
    )


@dataclasses.dataclass
class PendingStep:
    """One dispatched-but-not-reconciled device step.

    ``step_dispatch`` builds metadata, issues the (async) device call and
    returns one of these; ``step_complete`` performs the step's SINGLE
    host fetch and the post-fetch bookkeeping (emits, stop conditions,
    slot frees).  ``rows`` snapshots the slot occupants at dispatch so a
    completion that runs after the slot set changed (async pipeline:
    step N+1 completes after step N's finishes freed slots) can never
    attribute tokens to a later occupant — a row whose slot no longer
    holds the same request discards its tokens, exactly the fused-window
    overrun contract."""

    kind: str                   # "decode" | "spec" | "mixed"
    rows: list                  # [(slot_index, Request)] at dispatch
    handles: tuple              # device arrays the completion fetches
    n: int = 1                  # fused window size (decode)
    n_extra: int = 0            # fused tail length (spec)
    draft_len: Optional[np.ndarray] = None   # [B] (spec)
    # deferred first tokens (an admission wave's, a final chunk's):
    # [(Request, [R] device handle, row)], fetched inside this step's one
    # device_get instead of their own
    pending_first: list = dataclasses.field(default_factory=list)
    # the tokens of the rows that decoded inside the admission waves
    # launched ahead of this step: [(rows at the wave's launch, [B, W]
    # device handle)] in launch order, fetched in the same device_get and
    # emitted after the first tokens and ahead of the step's own
    waves: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class PreemptedSeq:
    """A decoder swapped out to host RAM, parked for exact resume.

    Private page CONTENTS live in the engine's ``HostPagePool`` keyed
    ``("seq", req.id, table_pos)`` and pinned; this record keeps the
    book-keeping needed to rebuild the slot bit-identically: the table
    layout (shared prefix pages keep their device page ids — their
    refcounts stay held while parked), decode position, last token,
    the evolved PRNG key, and the output-token histogram."""

    req: "Request"
    table: np.ndarray           # first n_pages entries of the page table
    private_pos: list           # table indices whose pages were spilled
    position: int
    last_token: int
    mrope_delta: int
    key: np.ndarray             # evolved per-slot PRNG key, [2] u32
    counts: np.ndarray          # output-token histogram, [V] i32
    preempted_at: float = dataclasses.field(default_factory=time.monotonic)
    # imported-snapshot path (ISSUE 11): page contents carried INLINE
    # (already checksum-verified at import) instead of through the host
    # pool — a migrated-in request must park and resume even on engines
    # whose host tier is off.  None = the PR 6 host-pool path.
    entries: Optional[list] = None


# ---------------------------------------------------------------------------
# portable request snapshots (ISSUE 11): export / migrate / import
# ---------------------------------------------------------------------------

SNAPSHOT_VERSION = 1


class SnapshotError(ValueError):
    """A request snapshot that must not touch the engine: wrong version,
    incompatible KV geometry, or a failed page checksum.  ``code`` is the
    typed discriminator surfaced to HTTP callers."""

    def __init__(self, message: str, code: str = "snapshot_invalid"):
        super().__init__(message)
        self.code = code


@dataclasses.dataclass
class RequestSnapshot:
    """One in-flight request as a first-class, portable object.

    Everything a peer engine with the same weights needs to continue the
    generation bit-identically: prompt + already-emitted token ids, the
    device-evolved sampler state captured via the PR 6 preempt path
    (evolved PRNG key, output-token penalty histogram, decode position),
    the sequence's KV pages in their STORED representation (raw int8
    codes + scales for quantized pools — restore is bit-exact), and the
    tenant/trace/sched-class identity so accounting follows the request
    across runners.  ``pages`` hold numpy array dicts (the
    ``gather_pages`` field layout); ``page_checksums`` are blake2b
    digests over the stored representation, verified by
    ``import_request`` BEFORE any allocator mutation."""

    version: int
    model: str
    request_id: str
    prompt_tokens: list
    output_tokens: list
    sampling: dict              # dataclasses.asdict(SamplingParams)
    stop_token_ids: list
    tenant: str
    trace_id: str
    sched_class: str
    max_len: Optional[int]
    preempt_count: int
    # device-evolved decode state; position None = the request never
    # reached a slot (queued / mid-chunk) and replays from the prompt
    position: Optional[int]
    last_token: Optional[int]
    mrope_delta: int
    key: Optional[list]         # evolved PRNG key, two uint32 words
    token_counts: dict          # SPARSE {token_id: count} histogram
    # KV geometry the importer validates before anything else
    page_size: int
    num_layers: int
    kv_heads: int
    head_dim: int
    kv_dtype: str
    pages: list                 # [{k, v, k_scale, v_scale}, ...] numpy
    page_checksums: list        # blake2b hex digest per page
    # table capacity the peer must allocate (>= len(pages)): only pages
    # holding WRITTEN KV ship — wire size scales with progress, not
    # max_tokens — and the importer backs the table's tail with fresh
    # (content-irrelevant) pages up to this count
    total_pages: int = 0
    # multi-LoRA adapter id (ISSUE 15): the importer re-resolves it
    # against ITS residency ladder, so a migrated adapter request keeps
    # decoding through the same adapter on the peer; "" = base model
    # (absent on pre-ISSUE-15 wire snapshots — default keeps them valid)
    adapter: str = ""

    @property
    def has_kv(self) -> bool:
        return self.position is not None and bool(self.pages)


# Compiled step functions are cached at module level keyed by the static
# configuration, NOT per Engine instance — two Engines serving the same
# architecture (or the same Engine recreated by a profile swap) reuse one
# executable.  Combined with jax's persistent compilation cache this makes
# profile hot-swap cheap (SURVEY.md §7 hard part #2).
#
# Since the ragged unification there is ONE such builder for the whole
# device step (``_build_ragged_step_fn``, keyed only on the prefill
# token-bucket at runtime) — packed/cache-hit prefill, chunked prefill,
# plain decode, the mixed step and spec-verify are host-side metadata
# builders over it.  The VL single-shot prefill (image-bucket shapes) and
# the embed splice are the only other compiled entry points;
# ``tools/lint_metrics.py`` contract 6 fails the build if a new lru-cached
# step builder appears outside this set.
def _mesh_sp(mesh) -> int:
    if mesh is not None and "sp" in mesh.axis_names:
        return mesh.shape["sp"]
    return 0


def _gather_history(layer_cache, idx, B: int, Hs: int):
    """Gather a slot-history window from the paged pool: ``idx`` indexes
    pages ([m] for the single-sequence chunk path, [B, m] for the batched
    verify path), reshaped token-major to [B, Hs, KVH, D].  Int8 pools
    dequantize in-register with the per-(slot, head) scales right after
    the gather (the gather itself moved 1 byte/elem) — the ONE recipe
    shared by chunk prefill, the mixed step, and speculative verify."""
    kp, vp = layer_cache[0], layer_cache[1]   # [N, P, KVH, D]
    _, P, KVH, D = kp.shape
    kh = kp[idx].reshape(B, Hs, KVH, D)
    vh = vp[idx].reshape(B, Hs, KVH, D)
    if len(layer_cache) == 4:
        ks, vs = layer_cache[2], layer_cache[3]
        kh = kh.astype(jnp.float32) * ks[idx].reshape(B, Hs, KVH)[..., None]
        vh = vh.astype(jnp.float32) * vs[idx].reshape(B, Hs, KVH)[..., None]
    return kh, vh


@functools.lru_cache(maxsize=64)
def _build_prefill_fn_mrope(model_cfg: ModelConfig, page_size: int, backend):
    """Qwen2-VL-family prefill: takes spliced input embeddings + 3-stream
    mrope positions; masking/KV-writes stay sequence-indexed."""
    from helix_tpu.models.qwen2_vl import text_forward_mrope

    cfg = model_cfg

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_fn(
        params, cache, tokens, embeds, positions3, page_table, length,
        sampling, key,
    ):
        B, S = tokens.shape  # B == 1
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        valid = positions < length
        seg = valid.astype(jnp.int32)

        def attn_fn(q, k, v, layer_cache, pos):
            return full_attention(
                q, k, v,
                causal=True,
                q_positions=pos,
                kv_positions=pos,
                q_segment_ids=seg,
                kv_segment_ids=seg,
                backend=backend,
            )

        logits, (k_new, v_new) = text_forward_mrope(
            params, cfg, tokens, positions3,
            attn_fn=attn_fn,
            input_embeds=embeds,
            mrope_sections=cfg.mrope_sections,
            seq_positions=positions,
        )
        pages, offsets = slot_to_page_offset(positions, page_table, page_size)
        cache = write_kv(cache, k_new, v_new, pages, offsets, valid)
        last = logits[jnp.arange(B), length - 1]
        token = sample(last, sampling, key[None])
        return cache, token

    return prefill_fn


@functools.lru_cache(maxsize=16)
def _build_embed_splice_fn(model_cfg: ModelConfig):
    """tokens [1,S] + padded image embeds [N, E] + their target indices ->
    spliced input embeddings (bucketed on N by the caller)."""
    cfg = model_cfg

    @jax.jit
    def splice(params, tokens, img_embeds, img_pos, n_img):
        from helix_tpu.ops.quant import embed_lookup

        emb = embed_lookup(params["embed"], tokens, jnp.dtype(cfg.dtype))
        S = tokens.shape[1]
        idx = jnp.where(
            jnp.arange(img_embeds.shape[0]) < n_img, img_pos, S + 1
        )
        emb = emb[0].at[idx].set(
            img_embeds.astype(emb.dtype), mode="drop"
        )[None]
        return emb

    return splice


def _pin_default_layout(cache):
    # Keep the page pools in their argument (row-major) layout through
    # the scan carry: without the pin, XLA:TPU's layout assignment
    # favours the KV scatter and relaids BOTH pools at the loop
    # boundary — two pool-sized HLO-temp copies per call, which alone
    # OOMed an 8B config on a 16 GiB chip (+4 GiB).
    from jax.experimental.layout import Layout, with_layout_constraint

    from helix_tpu.engine.kv_cache import PagedKVCache

    def pin(x):
        return with_layout_constraint(
            x, Layout(major_to_minor=tuple(range(x.ndim)))
        )

    # (the state pool is not the pages': it stays as it is)
    return dataclasses.replace(cache, **{
        f.name: pin(getattr(cache, f.name))
        for f in dataclasses.fields(cache)
        if f.name != "state" and getattr(cache, f.name) is not None})


class UnsupportedForModel(ValueError):
    """An engine setting the served model's architecture cannot take
    (raised when the engine is built, i.e. at profile apply)."""


# What an architecture is not served with: (engine setting, what of the
# model meets it, why).  Each row is refused by name when the engine is
# built, rather than run on a path that was never written for a pool with
# no head axis, or with a sequence's recurrent state left behind.  A kind of
# page and a kind of layer with a per-sequence state bring their rows in
# their records (``models/mixers.py``: ``refused_as``, ``refusals`` by these
# keys).
_SETTINGS = {
    "multi_device": (
        "a mesh of more than one device",
        lambda cfg, mesh: mesh is not None and mesh.devices.size > 1),
    "int8_kv": ("kv_cache_dtype int8",
                lambda cfg, mesh: cfg.kv_cache_dtype == "int8"),
    "adapters": ("adapter_pool_slots > 0",
                 lambda cfg, mesh: cfg.adapter_pool_slots > 0),
    "spec_decode": ("enable_spec_decode",
                    lambda cfg, mesh: cfg.enable_spec_decode),
    "tiered": ("ctx_hot_pages > 0", lambda cfg, mesh: cfg.ctx_hot_pages > 0),
    "host_tier": ("host_pool_bytes > 0",
                  lambda cfg, mesh: cfg.host_pool_bytes > 0),
    "prefix_cache": ("enable_prefix_cache",
                     lambda cfg, mesh: cfg.enable_prefix_cache),
}
_PACKED_HEADS = ("kv heads packed into one lane tile (head width under "
                 "128)", lambda m: m.kv_head_pack > 1)
_HELD_EXPERTS = ("held experts (one expert-parallel rank of the routed "
                 "experts)", lambda m: m.held_experts is not None)


def _kind_rows(kind, has) -> tuple:
    """A kind's rows of ``_REFUSALS``, from its record."""
    prop = (kind.refused_as, functools.partial(has, kind))
    return tuple((setting, prop, why) for setting, why in kind.refusals)


# the first row met is the one raised, so the rows stand in the order they
# were written: the page kinds' but the last's, the state kinds' in
# ``STATE_MIXERS``' order, the packed heads' row behind the first kind's, the
# held experts' behind the third's, the last page kind's (the index-key
# pool's) last.  A page kind whose pools stand beside another's (``base``) is
# refused what that one is.
_PAGE_ROWS = [
    _kind_rows(kind, lambda kind, m: kind in (
        m.page_kind, m.page_kind.base))
    for kind in PAGE_KINDS.values()]
_KIND_ROWS = [_kind_rows(kind, lambda kind, m: m.state_kind is kind)
              for kind in STATE_MIXERS.values()]
_REFUSALS = (
    *(row for rows in _PAGE_ROWS[:-1] for row in rows),
    *_KIND_ROWS[0],
    ("int8_kv", _PACKED_HEADS, "an int8 pool's scales are one a kv head"),
    *_KIND_ROWS[1],
    *_KIND_ROWS[2],
    ("multi_device", _HELD_EXPERTS,
     "the other ranks' experts and the exchange with them are not run: "
     "one chip computes its own experts' part of the sum"),
    *(row for rows in _KIND_ROWS[3:] for row in rows),
    *_PAGE_ROWS[-1],
)


def refuse_unsupported(model_cfg, cfg, mesh) -> None:
    """Raise :class:`UnsupportedForModel` for the first row of
    ``_REFUSALS`` that the engine settings and the model both meet."""
    for key, (prop, has), why in _REFUSALS:
        setting, is_set = _SETTINGS[key]
        if has(model_cfg) and is_set(cfg, mesh):
            raise UnsupportedForModel(
                f"{model_cfg.name}: {prop} is not served with {setting} "
                f"({why})"
            )


def _refuse_call(model_cfg, what: str) -> None:
    """Paths that move a sequence's pages and are asked for by a call, not
    a setting: refused for a model whose sequences carry a state too, or
    whose pages are pages of two pools."""
    for kind in (model_cfg.state_kind, model_cfg.page_kind):
        if kind is not None and kind.call_refusal:
            raise UnsupportedForModel(
                f"{model_cfg.name}: {kind.refused_as} is not served with "
                f"{what} ({kind.call_refusal})"
            )


def _fresh_kv_zeros(cfg: ModelConfig, B: int, S: int):
    """The two ``[L, B, S, ...]`` accumulators a forward pass fills with
    each layer's fresh cache entries (K and V, or latent and rope key)
    for the one ``write_kv`` scatter after it."""
    kdt = jnp.dtype(cfg.dtype)
    return tuple(
        jnp.zeros((cfg.num_attn_layers, B, S) + shp, kdt)
        for shp in cfg.kv_token_shapes()
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _restore_state_fn(pool, slot, state):
    return pool.at[:, slot].set(state.astype(pool.dtype))


def _cache_from(pc, cache: PagedKVCache) -> PagedKVCache:
    """The cache back from a forward pass's pool carry, which ends in the
    state pool iff ``cache`` has one."""
    if cache.state is None:
        return PagedKVCache.from_carry(pc)
    return PagedKVCache.from_carry(pc[:-1], pc[-1])


def _segments_fn(fn_p, fn_s, n_tok: int, split, join):
    """A mixer's look-back over the step's whole token axis, from one
    function a segment: the first ``n_tok`` arguments are token arrays and
    ``split`` into (prefill tokens, state rows); the prefill rows run first
    and the state rows behind them ON THE SAME CARRY (their slots are
    disjoint: a prompt in flight does not decode), and the outputs
    ``join`` back onto the axis.  ``fn_p`` None: the axis is the state
    rows alone and ``fn_s`` is the whole of it."""
    if fn_p is None:
        return fn_s

    def fn(*args):
        *xs, (carry, lc) = args
        parts = [split(x) for x in xs[:n_tok]]
        with jax.named_scope("prefill"):
            y_p, carry = fn_p(
                *(p for p, _ in parts), *xs[n_tok:], (carry, lc))
        with jax.named_scope("state"):
            y_s, carry = fn_s(
                *(r for _, r in parts), *xs[n_tok:], (carry, lc))
        return join(y_p, y_s), carry

    return fn


def _state_rows_fn(cfg, rows_s, backend, window, rows_p=None, split=None,
                   join=None, packed=None):
    """``forward``'s ``state_fn`` for the model's layers with a
    per-sequence state, from their kind's record: ``rows_s = (t0, qlen,
    hist, slots)`` the state rows (one token each, row ``b`` slot ``b``),
    ``window = (step, n_extra)`` which decode step of the program's fused
    window of ``1 + n_extra`` they are, ``rows_p = (t0, qlen, hist, slots,
    snap)`` the prefill rows before them on the axis, if the program has
    any.  ``packed``: the prefill rows have no history
    (``models/mixers.py::_window_rows_fn``)."""
    kind = cfg.state_kind
    return _segments_fn(
        rows_p and kind.rows_fn(
            rows_p[:4], backend, cfg=cfg, decode=False, snap=rows_p[4],
            packed=packed),
        kind.rows_fn(rows_s, backend, cfg=cfg, decode=True, window=window),
        kind.token_args, split, join)


def _ring_chunk_attention(q, k, v, caches, lyr, p_pos, p_seg, p_hist,
                          p_tables, mesh, page_size, hist_pages):
    """Sequence-parallel chunk-vs-history attention over the ICI ring
    (``sp`` mesh axis > 1): each chip holds a KV shard and ``ppermute``
    rotates shards — contexts beyond one chip's activation budget
    prefill sequence-parallel.  Ring attention has no segment ids, so
    the engine keeps history-attending rows ALONE in their call on sp
    meshes (padding KV slots get a sentinel position instead).

    ``hist_pages`` is the STATIC pow2-bucketed history capacity (part of
    the builder key, like the pre-unification chunk path): the gather
    and the ring payload scale with actual history, not max context."""
    from helix_tpu.parallel.ring_attention import ring_attention

    layer_view = tuple(c[lyr] for c in caches)
    Hs = hist_pages * page_size
    kh, vh = _gather_history(layer_view, p_tables[0, :hist_pages], 1, Hs)
    k_all = jnp.concatenate([kh.astype(k.dtype), k], axis=1)
    v_all = jnp.concatenate([vh.astype(v.dtype), v], axis=1)
    kv_pos_hist = jnp.arange(Hs)[None]
    kseg_hist = (kv_pos_hist < p_hist[0]).astype(jnp.int32)
    kv_pos = jnp.concatenate([kv_pos_hist, p_pos], axis=1)
    kseg = jnp.concatenate(
        [kseg_hist, (p_seg > 0).astype(jnp.int32)], axis=1
    )
    kv_pos_m = jnp.where(kseg > 0, kv_pos, 1 << 30)
    return ring_attention(
        q, k_all, v_all, mesh,
        q_positions=p_pos,
        kv_positions=kv_pos_m,
        causal=True,
    )


def _decode_forward(params, cache, state: DecodeState, *, cfg, backend,
                    window, use_adapters: bool = False, mesh=None):
    """One plain decode forward over every slot (each active slot a
    one-token row over its ragged paged history), nothing written:
    returns ``(logits [B, 1, V], (pool carry, fresh K, fresh V))``, the
    pool carry ending in the state pool where the model has one
    (``window``: ``_state_rows_fn``'s)."""
    B = state.last_token.shape[0]
    tokens = state.last_token[:, None]
    pos2d = state.positions[:, None]
    active = state.active
    t0 = jnp.arange(B, dtype=jnp.int32)
    q_len = (active > 0).astype(jnp.int32)
    hist = state.positions * active
    kacc0, vacc0 = _fresh_kv_zeros(cfg, B, 1)

    paged = cfg.page_kind.attend(
        (t0, q_len, hist), state.page_tables, backend, cfg=cfg, mesh=mesh)

    def attn_fn(q, k, v, carry_cache, pos, *more):
        (caches, kacc, vacc, *rest), lyr = carry_cache
        out = paged(q, k, v, *more, caches, lyr)
        return out, (caches, kacc.at[lyr].set(k), vacc.at[lyr].set(v),
                     *rest)

    carry0 = (cache.carry(), kacc0, vacc0)
    state_fn = None
    if cache.state is not None:
        # the slots' recurrent states ride the carry beside the pages
        carry0 += (cache.state,)
        state_fn = _state_rows_fn(
            cfg, (t0, q_len, hist, t0), backend, window)
    if cfg.mrope_sections is not None:
        from helix_tpu.models.qwen2_vl import text_forward_mrope

        # past the prompt, all three streams advance together at a
        # per-request constant offset from the sequence index
        pos3 = jnp.broadcast_to(
            (state.positions + state.mrope_delta)[None, :, None],
            (3, B, 1),
        )
        logits, (pc, kacc, vacc) = text_forward_mrope(
            params, cfg, tokens, pos3,
            attn_fn=attn_fn,
            carry_caches=carry0,
            mrope_sections=cfg.mrope_sections,
            seq_positions=pos2d,
        )
    else:
        logits, (pc, kacc, vacc, *pool) = forward(
            params, cfg, tokens, pos2d,
            attn_fn=attn_fn,
            carry_caches=carry0,
            # inactive slots never consume expert capacity: outputs
            # are independent of batch-mates (decode is dropless too)
            moe_token_mask=(active > 0)[:, None],
            moe_backend=backend,
            adapter_ids=(
                state.adapter_slots[:, None] if use_adapters else None
            ),
            state_fn=state_fn,
        )
        if pool:
            pc = pc + (pool[0],)
    return logits, (pc, kacc, vacc)


def _tail_decode_step(params, cache, state: DecodeState, *, cfg, backend,
                      page_size, window, use_adapters: bool = False,
                      mesh=None):
    """Traced body of ONE plain decode step over every slot.  This is the
    fused-window TAIL of the unified step (scanned ``n_extra`` times
    inside the same jit so a multi-token window still costs one host
    sync), bit-compatible with the pre-unification ``_decode_one_step``:
    same penalty → key-split → sample order, same garbage-page routing
    for parked slots."""
    B = state.last_token.shape[0]
    active = state.active
    logits, (pc, kacc, vacc) = _decode_forward(
        params, cache, state, cfg=cfg, backend=backend, window=window,
        use_adapters=use_adapters, mesh=mesh,
    )
    cache = _cache_from(pc, cache)
    pages, offsets = slot_to_page_offset(
        state.positions[:, None], state.page_tables, page_size
    )
    cache = write_kv(cache, kacc, vacc, pages, offsets,
                     (active > 0)[:, None])
    with jax.named_scope("sample"):
        penalised = apply_penalties(
            logits[:, 0], state.token_counts,
            state.sampling.presence, state.sampling.frequency,
        )
        carry_keys, step_keys = split_keys(state.keys)
        token = sample(penalised, state.sampling, step_keys)
    new_state = DecodeState(
        last_token=token,
        positions=state.positions + active,   # inactive slots stay parked
        page_tables=state.page_tables,
        active=active,
        mrope_delta=state.mrope_delta,
        keys=carry_keys,
        token_counts=state.token_counts.at[jnp.arange(B), token].add(
            active
        ),
        adapter_slots=state.adapter_slots,
        sampling=state.sampling,
    )
    return cache, new_state, token


@functools.lru_cache(maxsize=256)
def _build_ragged_step_fn(
    model_cfg: ModelConfig, page_size: int, backend, mesh,
    token_bucket: int, has_hist: bool, prefill_rows: int,
    state_width: int, n_tail_max: int, ring_hist_pages: int = 0,
    adapter_slots: int = 0, cold_chunks: int = 0, cold_ct: int = 0,
):
    """THE unified device step: ONE compiled entry point serves every
    caller, keyed at runtime only on the prefill token-bucket.

    One call runs, in one jit, ONE pass over the layers (one ``forward``,
    one carry): the prefill tokens and the state rows lie behind one
    another on one flat token axis ``[1, token_bucket + B * state_width]``,
    so every weight (norms, projections, MLP or experts, the head) is
    streamed once a program.  Only the token mixers are a segment's own:
    ``attn_fn`` and the state kinds' look-back (``_state_rows_fn``) split the
    axis at ``token_bucket`` (static), run the prefill rows and then the
    state rows on the same pool carry (their pages and slots are disjoint:
    a prompt in flight does not decode), and join the outputs.  The pass
    ends in hidden rows, and the head reads only the rows that sample.

    1. **Prefill rows** (``token_bucket`` > 0): up to ``prefill_rows``
       ragged rows — cold packed prompts, prefix-cache hits (their
       remainder attends the shared pages via ``hist``) and the in-flight
       long-prompt chunk all share the flat axis.  One ``write_kv``
       scatter, one batched first-token sample over each row's last
       token.  ``has_hist`` statically selects between pure packed
       self-attention (no pool reads — the cold common case) and the
       ragged paged op; an ``sp`` mesh routes single-row history chunks
       through ring attention instead.
    2. **State rows**: every decode slot is a ``state_width``-token
       row — its last sampled token plus up to ``state_width - 1``
       host-drafted speculative tokens (``draft_len[b]`` of them; 0 = a
       plain decode step, -1 = the slot sits this call out, e.g. during
       an admission wave: it stays on the axis, a row in the pass's
       products, masked out of routing, attention and every write).
       Verification is in-call: every live position
       samples from the slot's OWN SamplingParams with the penalty
       histogram evolved along the drafted prefix ("sample from target
       and compare" IS rejection sampling for a point-mass draft, so the
       output distribution is exactly non-speculative and greedy is
       bit-identical); the longest agreeing prefix is kept and
       positions/last_token/histogram roll back INSIDE the call.
       Rejected drafts' KV lands only in the slot's private page tail
       and is overwritten by the next step.  Key splits are consumed
       only at live positions, so a plain step costs exactly one split —
       the same key stream plain decode always had.  With no prefill rows
       (``token_bucket`` 0) the axis is the state rows' ``[B,
       state_width]`` alone.
    3. **Fused tail**: ``n_extra`` (DYNAMIC — no shape per window size)
       plain decode steps scanned onto the rolled-back state inside the
       same jit, so one host sync still yields a full
       ``decode_steps_per_sync`` window.

    Pre-unification this was six lru-cached builders × their bucket
    grids (packed buckets, chunk C×hist pairs, mixed pairs, per-window
    decode scans, verify width×hist×tail triples).  Now the compiled
    set is O(|token ladder|); ``engine/ragged.py``'s registry records
    each entry for the ``helix_compiled_step_shapes`` gauge.
    """
    ragged_meta.note_step_shape(
        (model_cfg, page_size, backend, mesh),
        ("ragged", token_bucket, has_hist, prefill_rows,
         ring_hist_pages, cold_chunks),
    )
    # adapter_slots is an ENGINE-WIDE constant (EngineConfig), not a
    # per-call shape axis: every existing trace family gains exactly
    # one variant, so the compiled-shape count is unchanged vs the
    # pool-less engine (the tentpole's no-new-trace-families contract;
    # adapter LOADS write values into the same-shaped pool arrays and
    # never retrace)
    use_adapters = adapter_slots > 0
    cfg = model_cfg
    # layers with a per-sequence state: every row reads and writes its
    # slot's; where the kind files snapshots, the prefill rows also hand back
    # the state at one page boundary each (what a prefix hit resumes from)
    has_state = cfg.state_kind is not None
    has_snaps = has_state and cfg.state_kind.snapshots
    has_window = has_state and cfg.state_kind.window is not None
    is_moe = cfg.num_experts > 0
    is_mrope = cfg.mrope_sections is not None
    Cb = token_bucket
    W = state_width
    # sp meshes run single-row chunks (cold first chunk included —
    # sharding the 32k chunk's self-attention is the point) through
    # ring attention; multi-row packed waves keep segment-masked
    # full attention like the pre-unification packed path
    use_ring = _mesh_sp(mesh) > 1 and Cb > 0 and prefill_rows == 1
    if is_mrope and Cb > 0:
        raise ValueError(
            "mrope prompts prefill through the VL single-shot builder, "
            "never the ragged prefill segment"
        )

    def step_fn(params, cache, state: DecodeState, pargs, drafts,
                draft_len, n_extra, cold=None):
        B = state.last_token.shape[0]
        drops = None
        snaps = None
        # tiered KV residency (ISSUE 20): staged cold-middle chunks plus
        # the per-row demoted token spans — one slab shared by the
        # prefill segment (rows = plan rows, via c_prow) and the state
        # segment (rows = decode slots, via c_srow); a chunk owned by
        # neither mapping carries row -1 and masks to an exact zero
        # contribution
        if cold_chunks > 0:
            (c_k, c_v, c_ks, c_vs, c_prow, c_srow, c_len,
             p_span_lo, p_span_hi, s_span_lo, s_span_hi) = cold
            p_cold = (c_k, c_v, c_ks, c_vs, c_prow, c_len,
                      p_span_lo, p_span_hi)
            s_cold = (c_k, c_v, c_ks, c_vs, c_srow, c_len,
                      s_span_lo, s_span_hi)
        else:
            p_cold = None
            s_cold = None

        # ---- the state rows (decode / verify) ------------------------
        tokens_s = jnp.concatenate(
            [state.last_token[:, None], drafts], axis=1
        )                                                        # [B, W]
        pos_s = state.positions[:, None] + jnp.arange(W)[None]
        act = state.active > 0
        live = (
            (jnp.arange(W)[None] <= draft_len[:, None]) & act[:, None]
        )
        s_t0 = jnp.arange(B, dtype=jnp.int32) * W
        # rows sitting this call out (draft_len -1: admission waves,
        # standalone chunk steps) get q_len 0 so the kernel skips their
        # page-pool sweep entirely; they stay on the token axis (a static
        # shape) and cost rows in the pass's products, not a pass
        s_qlen = jnp.where(
            act & (draft_len >= 0), W, 0
        ).astype(jnp.int32)
        s_hist = state.positions * state.active
        # a live slot beside a state pool is a one-token row over its own
        # state (W is 1 there: speculation is refused)
        rows_s = (s_t0, live[:, 0].astype(jnp.int32), s_hist,
                  jnp.arange(B, dtype=jnp.int32))

        # ---- the prefill rows, and the one token axis -----------------
        if Cb > 0:
            if has_state:
                *pargs, p_slots, p_snap = pargs
            if use_adapters:
                (p_tokens, p_pos, p_seg, p_pages, p_offsets, p_t0,
                 p_qlen, p_hist, p_tables, p_ends, p_sampling, p_keys,
                 p_aids) = pargs
            else:
                (p_tokens, p_pos, p_seg, p_pages, p_offsets, p_t0,
                 p_qlen, p_hist, p_tables, p_ends, p_sampling,
                 p_keys) = pargs
                p_aids = None

            def split(x):
                """``[1, Cb + B * W, ...]`` -> the prefill tokens ``[1, Cb,
                ...]`` and the state rows ``[B, W, ...]``."""
                return x[:, :Cb], x[:, Cb:].reshape((B, W) + x.shape[2:])

            def join(xp, xs):
                return jnp.concatenate(
                    [xp, xs.reshape((1, B * W) + xs.shape[2:])], axis=1)

            rows_p = ((p_t0, p_qlen, p_hist, p_slots, p_snap)
                      if has_state else None)
            tokens, pos = join(p_tokens, tokens_s), join(p_pos, pos_s)
            moe_mask = join(p_seg > 0, live)
        else:
            split = join = rows_p = None
            tokens, pos, moe_mask = tokens_s, pos_s, live
        aids = None
        if use_adapters:
            aids = jnp.broadcast_to(state.adapter_slots[:, None], (B, W))
            if Cb > 0:
                aids = join(p_aids, aids)

        # the token mixer is a segment's own: the prefill tokens take their
        # branch, the state rows the ragged call with the one-token query
        # block, both over the same pools; each segment files its fresh K/V
        # for its own scatter (the accumulators are (prefill's, state's))
        # (the kind of the model's pages says how a segment attends over
        # the pool: ``models/mixers.py::PAGE_KINDS``; None: prefill rows
        # none of which has history read no page)
        pages = cfg.page_kind
        p_paged = pages.attend(
            (p_t0, p_qlen, p_hist), p_tables, backend, cfg=cfg, bucket=Cb,
            has_hist=has_hist, cold=p_cold, mesh=mesh) if Cb > 0 else None
        s_paged = pages.attend(
            (s_t0, s_qlen, s_hist), state.page_tables, backend, cfg=cfg,
            cold=s_cold, mesh=mesh)

        def p_attn(q, k, v, *more_cache):
            *more, carry_cache = more_cache
            (caches, (kp, ks), (vp, vs), *rest), lyr = carry_cache
            if use_ring:
                out = _ring_chunk_attention(
                    q, k, v, caches, lyr, p_pos, p_seg, p_hist,
                    p_tables, mesh, page_size, ring_hist_pages,
                )
            elif p_paged is not None:
                out = p_paged(q, k, v, *more, caches, lyr)
            else:
                # cold rows only: packed self-attention, no pool reads —
                # bit-compatible with the pre-unification packed-prefill
                # path
                out = full_attention(
                    q, k, v,
                    causal=True,
                    q_positions=p_pos,
                    kv_positions=p_pos,
                    q_segment_ids=p_seg,
                    kv_segment_ids=p_seg,
                    backend=backend,
                    mesh=mesh,
                )
            return out, (caches, (kp.at[lyr].set(k), ks),
                         (vp.at[lyr].set(v), vs), *rest)

        def s_attn(q, k, v, *more_cache):
            *more, carry_cache = more_cache
            (caches, (kp, ks), (vp, vs), *rest), lyr = carry_cache
            out = s_paged(q, k, v, *more, caches, lyr)
            return out, (caches, (kp, ks.at[lyr].set(k)),
                         (vp, vs.at[lyr].set(v)), *rest)

        attend = _segments_fn(
            p_attn if Cb > 0 else None, s_attn, pages.token_args, split, join)

        def attn_fn(q, k, v, carry_cache, pos, *more):
            return attend(q, k, v, *more, carry_cache)

        # ---- ONE pass over every layer --------------------------------
        with jax.named_scope("pass" if Cb > 0 else "state"):
            kacc_p, vacc_p = (_fresh_kv_zeros(cfg, 1, Cb) if Cb > 0
                              else (None, None))
            kacc_s, vacc_s = _fresh_kv_zeros(cfg, B, W)
            carry0 = (cache.carry(), (kacc_p, kacc_s), (vacc_p, vacc_s))
            state_fn = None
            if has_state:
                # beside the pool, for this program alone, what its kind's
                # fused window keeps from step to step (empty: a program
                # starts from an exact pool and leaves one)
                carry0 += (cache.state if not has_window else (
                    *cache.state,
                    cfg.state_kind.window(cfg, B, n_tail_max + 1)),)
                if has_snaps and Cb > 0:
                    (shp, _), = cfg.state_arrays()
                    carry0 += (jnp.zeros(
                        (cfg.num_state_layers, prefill_rows) + shp,
                        cache.state.dtype),)
                state_fn = _state_rows_fn(
                    cfg, rows_s, backend, (0, n_extra), rows_p, split, join,
                    packed=((p_pos, p_seg, mesh)
                            if Cb > 0 and not has_hist else None))
            if is_mrope:
                from helix_tpu.models.qwen2_vl import text_forward_mrope

                pos3 = jnp.broadcast_to(
                    (pos_s + state.mrope_delta[:, None])[None], (3, B, W)
                )
                logits_s, (pc, kacc, vacc) = text_forward_mrope(
                    params, cfg, tokens_s, pos3,
                    attn_fn=attn_fn,
                    carry_caches=carry0,
                    mrope_sections=cfg.mrope_sections,
                    seq_positions=pos_s,
                )
                rest = ()
            else:
                res = forward(
                    params, cfg, tokens, pos,
                    attn_fn=attn_fn,
                    carry_caches=carry0,
                    return_hidden=True,
                    moe_token_mask=moe_mask,
                    moe_backend=backend,
                    return_moe_stats=is_moe,
                    adapter_ids=aids,
                    moe_decode_rows=B * W if Cb > 0 else 0,
                    state_fn=state_fn,
                )
                hidden, (pc, kacc, vacc, *rest) = res[:2]
                if is_moe:
                    # one product, one report: routed and dropped are the
                    # program's; load ratio, experts touched and tile fill
                    # are of its prefill tokens and decode rows together
                    drops = res[2]["vector"]
                # the head reads the rows that sample: each prefill row's
                # last token and the state rows
                if Cb > 0:
                    h_p, h_s = split(hidden)
                    logits = lm_head(
                        params, cfg, join(h_p[:, p_ends], h_s))[0]
                    logits_p = logits[:prefill_rows]          # [R, V]
                    logits_s = logits[prefill_rows:].reshape(B, W, -1)
                else:
                    logits_s = lm_head(params, cfg, hidden)
            if has_state:
                pool, *snap_out = rest
                snaps = snap_out[0] if snap_out else None
                cache = PagedKVCache.from_carry(pc, pool)
            else:
                cache = PagedKVCache.from_carry(pc)

        if Cb > 0:
            with jax.named_scope("prefill"):
                cache = write_kv(
                    cache, kacc[0], vacc[0], p_pages, p_offsets, p_seg > 0,
                )
                with jax.named_scope("sample"):
                    p_first = sample(logits_p, p_sampling, p_keys)
        else:
            p_first = jnp.zeros((0,), jnp.int32)

        with jax.named_scope("state"):
            pages_s, offs_s = slot_to_page_offset(
                pos_s, state.page_tables, page_size
            )
            cache = write_kv(cache, kacc[1], vacc[1], pages_s, offs_s, live)

            # position-by-position penalised sampling (cheap [B, V] ops):
            # the histogram carries the drafted prefix forward so position
            # j's penalties match plain decode having emitted j tokens.
            # Splits are consumed ONLY at live positions — a plain step
            # (draft_len 0) advances the key stream exactly once.
            def samp_body(carry, j):
                counts, keys = carry
                pen = apply_penalties(
                    logits_s[:, j], counts,
                    state.sampling.presence, state.sampling.frequency,
                )
                carry_keys, step_keys = split_keys(keys)
                tok = sample(pen, state.sampling, step_keys)
                lj = live[:, j]
                tok = jnp.where(lj, tok, 0)
                keys = jnp.where(lj[:, None], carry_keys, keys)
                counts = counts.at[jnp.arange(B), tok].add(
                    lj.astype(counts.dtype)
                )
                return (counts, keys), tok

            with jax.named_scope("sample"):
                (counts, keys), sampled = jax.lax.scan(
                    samp_body, (state.token_counts, state.keys),
                    jnp.arange(W),
                )
            sampled = sampled.T                                  # [B, W]

            # acceptance: longest prefix of draws agreeing with the drafts
            if W > 1:
                in_draft = jnp.arange(W - 1)[None, :] < draft_len[:, None]
                agree = jnp.where(
                    in_draft, sampled[:, : W - 1] == drafts, True
                )
                prefix = jnp.cumprod(agree.astype(jnp.int32), axis=1)
                n_acc = jnp.sum(
                    prefix * in_draft.astype(jnp.int32), axis=1
                )
            else:
                n_acc = jnp.zeros((B,), jnp.int32)
            emit = jnp.where(live[:, 0], n_acc + 1, 0)           # [B]

            # roll back past the accepted length: positions/last_token/
            # histogram come out exactly as ``emit`` plain decode steps
            new_last = jnp.take_along_axis(
                sampled, jnp.maximum(emit - 1, 0)[:, None], axis=1
            )[:, 0]
            discard = (jnp.arange(W)[None, :] >= emit[:, None]) & live
            counts = counts.at[jnp.arange(B)[:, None], sampled].add(
                -discard.astype(counts.dtype)
            )
            new_state = DecodeState(
                last_token=jnp.where(
                    emit > 0, new_last, state.last_token
                ),
                positions=state.positions + emit,
                page_tables=state.page_tables,
                active=state.active,
                mrope_delta=state.mrope_delta,
                keys=keys,
                token_counts=counts,
                adapter_slots=state.adapter_slots,
                sampling=state.sampling,
            )

        # ---- 3. fused plain-decode tail (dynamic length) -------------
        if n_tail_max > 0:
            with jax.named_scope("tail"):
                buf0 = jnp.zeros((n_tail_max, B), jnp.int32)

                def tail_body(t, carry):
                    c, st, buf = carry
                    c, st, tok = _tail_decode_step(
                        params, c, st, cfg=cfg, backend=backend,
                        page_size=page_size,
                        window=(t + 1, n_extra) if has_window else None,
                        use_adapters=use_adapters, mesh=mesh,
                    )
                    return _pin_default_layout(c), st, buf.at[t].set(tok)

                cache, new_state, extra = jax.lax.fori_loop(
                    0, n_extra, tail_body,
                    (_pin_default_layout(cache), new_state, buf0),
                )
        else:
            extra = jnp.zeros((0, B), jnp.int32)
        if has_window:
            # the window's last step left it empty
            cache = dataclasses.replace(cache, state=cache.state[:-1])
        if has_state:
            return (cache, new_state, p_first, sampled, emit, extra, drops,
                    snaps)
        return cache, new_state, p_first, sampled, emit, extra, drops

    step_fn.__name__ = step_fn.__qualname__ = ragged_meta.step_program_name(
        token_bucket, has_hist, prefill_rows, ring_hist_pages, cold_chunks
    )
    return jax.jit(step_fn, donate_argnums=(1, 2))



class Engine:
    """Single-model serving engine on one mesh slice."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        params,
        cfg: EngineConfig,
        mesh=None,
        rng_seed: int = 0,
    ):
        self.model_cfg = model_cfg
        # the record of the model's kind of layer with a per-sequence state
        # (``models/mixers.py``), None where its memory is pages alone; and
        # the records of the kinds it has layers of, the kind of its pages
        # first: what is checked, counted and shown of a kind comes from here
        self.mixer = model_cfg.state_kind
        self.kinds = tuple(
            kind for kind, layers in (
                (model_cfg.page_kind, model_cfg.num_attn_layers),
                (self.mixer, model_cfg.num_state_layers)) if layers)
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        # chunked prefill assumes chunk/history shapes are page-aligned
        # powers of two (flash block divisibility + exact history gather)
        q, ps = cfg.max_prefill_len, cfg.page_size
        while q > ps and q % 2 == 0:
            q //= 2
        if q != ps:
            raise ValueError(
                f"max_prefill_len ({cfg.max_prefill_len}) must be "
                f"page_size ({ps}) times a power of two"
            )
        if cfg.kv_cache_dtype not in (
            "auto", None, "", "bfloat16", "float32", "int8"
        ):
            raise ValueError(
                f"unsupported kv_cache_dtype {cfg.kv_cache_dtype!r} "
                "(expected auto | bfloat16 | float32 | int8)"
            )
        refuse_unsupported(model_cfg, cfg, mesh)
        # resolved ONCE, here: on a TPU the Pallas kernels, on a CPU the
        # XLA references; nothing downstream re-decides or falls back
        from helix_tpu.ops.attention import head_shards, resolve_backend

        self._backend = resolve_backend(cfg.attn_backend)
        self.cache_cfg = cfg.cache_config(dtype=model_cfg.dtype)
        # bytes of the state pool (0 for a model without one)
        self.recurrent_state_bytes = self.cache_cfg.state_bytes(model_cfg)
        # ... and of each array of the page pool, by what it holds
        self._pool_bytes = {
            f"{holds}_pool_bytes": n
            for holds, n in self.cache_cfg.pool_bytes(model_cfg).items()}
        dev = (mesh.devices.flat[0] if mesh is not None
               else jax.devices()[0])
        if self._backend == "pallas":
            tp = head_shards(mesh)
            itemsize = jnp.dtype(self.cache_cfg.dtype).itemsize
            for kind in self.kinds:
                # each kind of layer at its own kernel's geometry
                if kind.check_geometry:
                    kind.check_geometry(model_cfg, tp, itemsize)
        logging.getLogger(__name__).info(
            "engine %s: attention backend %s on platform %s, device_kind "
            "%s, %d device(s)",
            model_cfg.name, self._backend, dev.platform, dev.device_kind,
            1 if mesh is None else mesh.devices.size,
        )
        self.cache = PagedKVCache.create(model_cfg, self.cache_cfg, mesh)
        self.allocator = PageAllocator(
            self.cache_cfg.num_pages, self.cache_cfg.max_pages_per_seq
        )
        B = cfg.max_decode_batch
        self.slots: list[Optional[Request]] = [None] * B
        self.waiting: list[Request] = []
        self._requests: dict[str, Request] = {}
        # host mirrors of device-visible per-slot state
        self._last_token = np.zeros((B,), np.int32)
        self._positions = np.zeros((B,), np.int32)
        self._mrope_delta = np.zeros((B,), np.int32)
        self._page_tables = np.zeros(
            (B, self.cache_cfg.max_pages_per_seq), np.int32
        )
        self._slot_keys = np.zeros((B, 2), np.uint32)   # per-slot carry keys
        self._state_dirty = True
        self._changed_slots: set[int] = set()  # admitted/freed since sync
        self._dstate: Optional[DecodeState] = None
        self._chunking: Optional[dict] = None  # in-flight chunked prefill
        from helix_tpu.engine.kv_cache import PrefixCache

        # a kind whose steps hand back boundary states: a prefix is pages
        # AND the state at its end, filed by the steps that pass a boundary
        self.prefix_cache = (
            PrefixCache(stateful=bool(self.mixer and self.mixer.snapshots))
            if cfg.enable_prefix_cache else None
        )
        # page boundaries of a prompt in flight whose state a step has
        # handed back: req id -> {pages: (the step's states, the row)},
        # filed under the chain digests when the prompt's pages are adopted
        self._boundary_states: dict[str, dict] = {}
        self.num_state_snapshots = 0
        self.num_state_restores = 0
        # the host's account of what the steps did to the page pool and the
        # state pool, by the keys of the kinds' records (``models/mixers.py``:
        # pages walked and bytes read, rows by the form that ran them, bytes
        # moved, chunks); a launch adds each kind's ``account`` to it, and an
        # empty launch's holds every key
        self.mixer_counts = {}
        for kind in self.kinds:
            if kind.account:
                self.mixer_counts.update(dict.fromkeys(kind.account(
                    model_cfg, self.cache_cfg, (), np.zeros(0, np.int64), 0),
                    0))
        # the live tokens the last launch's rows attended over
        self.step_context_tokens = 0
        # prefix hits cut back to a boundary with a state on file (or to
        # nothing) for want of one at the pages' end
        self.prefix_hits_shortened = 0
        self._kv_filestore = None
        self._shared_pages: dict[str, list] = {}  # req id -> cache pages
        # host-RAM KV tier (ISSUE 6): spilled prefix pages + swapped-out
        # decoders, byte-budgeted; None = tier off (evictions free pages,
        # preemption unavailable)
        from helix_tpu.engine.kv_cache import HostPagePool

        self.host_pool = (
            HostPagePool(cfg.host_pool_bytes)
            if cfg.host_pool_bytes > 0
            else None
        )
        # tiered KV residency (ISSUE 20): demoted cold-middle pages live
        # in the host pool keyed ("ctx", req_id, page_idx); each tiered
        # slot keeps a ledger {lo, hi, top, rid, table} — [lo, hi) is the
        # demoted span (pages zeroed in the table), top the high-water of
        # allocated device pages.  _cold_staged caches the assembled +
        # device_put chunk slab between steps so prefetch overlaps H2D
        # with the in-flight step's compute.
        if cfg.ctx_hot_pages > 0:
            if self.host_pool is None:
                raise ValueError(
                    "ctx_hot_pages > 0 requires host_pool_bytes > 0: "
                    "demoted cold pages live in the host page pool"
                )
            if model_cfg.mrope_sections is not None:
                raise ValueError(
                    "tiered KV residency is not supported for mrope (VL) "
                    "models"
                )
            if _mesh_sp(mesh) > 1:
                raise ValueError(
                    "tiered KV residency is not supported with sequence "
                    "parallelism (ring attention owns the history split)"
                )
            if cfg.ctx_stream_pages < 1:
                raise ValueError(
                    f"ctx_stream_pages ({cfg.ctx_stream_pages}) must be "
                    ">= 1"
                )
        self._tiered: dict[int, dict] = {}
        self._cold_staged: Optional[dict] = None
        self.num_ctx_stream_chunks = 0
        self.num_ctx_demoted_pages = 0
        self.preempted: list[PreemptedSeq] = []   # parked, resume FIFO
        self._resume_failures: list = []          # (req, reason) for the loop
        # scheduler delegation (serving/sched.py): the loop wires these.
        # on_admit fires once per confirmed admission (_try_claim
        # success) — the fair-share charge point; victim_policy, when
        # set, orders preempt_for_pressure candidates (None keeps the
        # builtin newest-admission/largest-footprint pick);
        # prefill_budget caps NEW prefill-admission tokens per step
        # (None = unbudgeted — the historical behaviour)
        self.on_admit: Optional[Callable[[Request], None]] = None
        # entered around every blocking read of the device (the step's
        # fetch, the admission wave's first-token fetch): the loop wires
        # its emission stage's gate here, so token delivery runs while
        # the host is parked and not between a completion and a launch
        self.device_wait = contextlib.nullcontext()
        self.victim_policy: Optional[Callable[[list], list]] = None
        self.prefill_budget: Optional[int] = None
        self._budget_left: Optional[int] = None
        # plan-broadcast hooks (serving/multihost_serving.py): a leader
        # wraps step_dispatch with a PlanRecorder that captures host
        # decisions (admits+cached_tokens, resumes, drafts, budget,
        # queue pressure) as data; a follower steps under a PlanDrive
        # that pins the same decisions to the leader's plan.  Both are
        # duck-typed so the engine never imports the serving layer.
        self._plan_recorder = None
        self._plan_drive = None
        self._slot_count_overrides: dict[int, np.ndarray] = {}
        # deferred first tokens (ISSUE 13 for a final chunk, ISSUE 33 for an
        # admission wave): the prefill's sampled tokens stay on device:
        # _sync_state patches the slots' DecodeState from the handle and
        # the emit joins the decode step's single device_get (one host
        # round trip per step, not two, and no fetch between a prefill's
        # launch and the decode step's).  _inflight_out counts a request's
        # dispatched-not-yet-reconciled tokens, a deferred first token
        # among them, so a dispatch on predicted state computes budgets and
        # headroom against post-step state.
        self._pending_first: list = []    # [(req, [R] dev handle, row)]
        self._pending_first_ids: set = set()
        self._pending_token_patches: dict[int, tuple] = {}  # slot -> (h, row)
        self._inflight_out: dict[str, int] = {}
        # an admission wave launches the running rows live (one decode
        # step inside the wave's pass); their tokens stay on the device as
        # the first tokens do and ride the next PendingStep:
        # [([(slot, Request)], [B, W] dev handle)] in launch order
        self._pending_waves: list = []
        # slots claimed by the admission pass under way: in ``slots``, but
        # their position and last token are seeded only when the pass ends
        # (``_finish_packed_admissions`` + ``_sync_state``), so they sit
        # out this pass's waves as a chunking request does
        self._admitting: set = set()
        # the ``active`` column the device state holds (a row whose budget
        # or page room the tokens in flight exhaust is launched inactive)
        self._active_sent = np.zeros((B,), np.int32)
        # host clock at the first device launch of the last
        # ``step_dispatch`` (None: it launched nothing); the engine loop
        # reads how long the device waited for the host from it
        self.first_launch_time: Optional[float] = None
        self._prefetched: set = set()   # digests with in-flight device puts
        self._key_base = _splitmix64(0x8E1_1C9 ^ (rng_seed & _M64))
        self._key_nonce = 0
        self._step_counter = itertools.count()
        # metrics
        self.num_prefill_tokens = 0
        self.num_decode_tokens = 0
        # every token handed to a subscriber (decode + prefill first
        # tokens) — the numerator of goodput tokens/s
        self.num_generated_tokens = 0
        # prefill-bucket padding: tokens of forward-pass work spent on
        # zeros because prompts round up to power-of-two buckets (the
        # padding-waste axis of the ragged-paged-attention analysis)
        self.num_prefill_padding_tokens = 0
        # requests admitted to a slot (flight-recorder admission deltas)
        self.num_admitted = 0
        # request-level prefix-cache outcomes, counted at claim time
        # (page-level hit/miss pools live on PrefixCache itself)
        self.prefix_cache_hits = 0
        self.prefix_cache_misses = 0
        # ragged mixed steps taken (chunk prefill + decode in ONE call)
        self.num_mixed_steps = 0
        # programs whose prefill rows and state rows shared one pass over
        # the layers (every program with a prefill segment), and the state
        # rows that rode those passes sitting out (draft_len -1)
        self.num_joint_pass_steps = 0
        self.num_joint_pass_inert_rows = 0
        # tokens decoded by running rows inside admission waves (a model
        # step a wave that once produced nothing)
        self.num_wave_decode_tokens = 0
        # --- speculative decoding (engine/spec.py) ---
        # host-side prompt-lookup drafter + per-request acceptance EMA;
        # None = speculation off (config, or an unsupported model family)
        self.spec = None
        if cfg.enable_spec_decode:
            if cfg.spec_tokens < 1:
                raise ValueError(
                    f"spec_tokens ({cfg.spec_tokens}) must be >= 1 when "
                    "enable_spec_decode is set"
                )
            if (
                model_cfg.mrope_sections is not None
                or model_cfg.num_experts > 0
            ):
                # mrope decode needs 3-stream positions the verify chunk
                # does not thread; MoE expert capacity is shared across
                # the chunk, which would perturb routing vs plain decode
                logging.getLogger(__name__).warning(
                    "speculative decoding is not supported for %s models"
                    " — running plain decode",
                    "mrope (VL)" if model_cfg.mrope_sections is not None
                    else "MoE",
                )
            else:
                from helix_tpu.engine.spec import SpecConfig, SpecDecoder

                self.spec = SpecDecoder(
                    SpecConfig(spec_tokens=cfg.spec_tokens)
                )
        # --- continuous multi-LoRA serving (ISSUE 15) ---
        # batched adapter pool (engine/adapters.py): one resident base
        # model, many per-tenant adapters — requests carry an adapter
        # id, every device-step row carries its pool slot, and the
        # unified step applies scale * (x @ A) @ B per token via a
        # batched gather-matmul.  None = off (config, or an unsupported
        # model family).  adapter_store is the host/filestore residency
        # ladder below the pool (built by default; the node agent may
        # re-wire a custom one post-construction like kv_filestore).
        self.adapter_pool = None
        self.adapter_store = None
        self._adapter_refs: dict[str, str] = {}   # req id -> adapter id
        self._slot_adapters = np.zeros((B,), np.int32)
        if cfg.adapter_pool_slots > 0:
            if model_cfg.mrope_sections is not None:
                logging.getLogger(__name__).warning(
                    "batched multi-LoRA serving is not supported for "
                    "mrope (VL) models — running without an adapter pool"
                )
            elif cfg.adapter_pool_slots < 2:
                # slot 0 is the reserved identity adapter, so one slot
                # can serve nothing — degrade to off (warn) instead of
                # failing the whole model's profile apply
                logging.getLogger(__name__).warning(
                    "adapter_pool_slots=%d leaves no usable slots "
                    "(slot 0 is the reserved identity) — running "
                    "without an adapter pool; set >= 2 to serve "
                    "adapters", cfg.adapter_pool_slots,
                )
            else:
                from helix_tpu.engine.adapters import (
                    AdapterPool,
                    default_adapter_store,
                )

                self.adapter_pool = AdapterPool(
                    model_cfg, cfg.adapter_targets, cfg.adapter_rank,
                    cfg.adapter_pool_slots,
                    dtype=jnp.dtype(model_cfg.dtype),
                )
                self.adapter_store = default_adapter_store(
                    model_cfg, cfg
                )
        self._grafted_params = None    # (pool.version, params) cache
        self._peek_fn = None           # next_token_logits' jit, on demand
        # --- unified ragged step (ISSUE 10) ---
        # ONE compiled device-step entry point serves packed/cache-hit
        # prefill, chunked prefill, plain decode, the mixed step and
        # spec-verify; at runtime it is keyed only on the prefill
        # token-bucket ladder below (HELIX_TOKEN_BUCKETS overrides the
        # power-of-two default with finer rungs → less padding, a few
        # more compiles).
        self._token_ladder = ragged_meta.parse_token_buckets(
            os.environ.get("HELIX_TOKEN_BUCKETS"),
            self.cache_cfg.page_size,
            cfg.max_prefill_len,
        )
        # fused-window tail capacity (static buffer; actual tail length
        # is a DYNAMIC argument, so every window size shares one trace)
        self._n_tail_max = max(0, cfg.decode_steps_per_sync - 1)
        W = self._spec_width()
        self._zero_drafts = np.zeros((B, W - 1), np.int32)
        self._zero_rows = np.zeros((B,), np.int32)     # plain decode rows
        self._inert_rows = np.full((B,), -1, np.int32)  # state rows sit out
        self._shape_key = (
            model_cfg, self.cache_cfg.page_size, self._backend, mesh,
        )
        # verify calls issued, drafts proposed, drafts accepted
        self.num_spec_steps = 0
        self.num_spec_drafted_tokens = 0
        self.num_spec_accepted_tokens = 0
        # device-side decode steps (each fused window of n counts n):
        # decode_tokens / (device_steps * batch) is exact slot utilization
        self.num_decode_device_steps = 0
        # device-step CALLS issued (one per unified ragged step / VL
        # prefill): (prefill + decode tokens) / calls is the
        # tokens-per-device-step figure the ragged unification moves
        self.num_device_calls = 0
        # KV tiering (ISSUE 6): swap-out/swap-in of running decoders and
        # cumulative host->device restore time (the numerator of
        # helix_kv_restore_seconds_total; page-level spill/restore
        # pools live on host_pool)
        self.num_preemptions = 0
        self.num_resumes = 0
        self.restore_seconds = 0.0
        # portable request snapshots (ISSUE 11): export/import counters
        # feed the helix_migrations_* series
        self.num_snapshots_exported = 0
        self.num_snapshots_imported = 0
        # disaggregated prefill/decode (ISSUE 14): snapshots exported at
        # prefill completion for a decode-pool peer (a subset of
        # num_snapshots_exported)
        self.num_prefill_exports = 0
        # persistent filestore KV tier (ISSUE 14): the bottom rung of
        # the residency ladder (HBM -> host RAM -> peer -> filestore).
        # Wired post-construction (serving.kv_filestore.filestore_for_
        # engine) like on_admit; None = tier off.  filestore_restored_
        # pages counts pages adopted FROM it (cross-restart prefix hits).
        self.filestore_restored_pages = 0
        # MoE routing assignments dropped to expert-capacity overflow
        # during prefill (those tokens silently rode the residual stream);
        # device scalars accumulate un-fetched and drain lazily so the
        # prefill hot path never blocks on a drop-counter device_get
        self._moe_dropped = 0
        self._moe_drop_handles: list = []
        # the routing load of MoE steps, from the same small array: routed
        # (token, choice) assignments, and of the last step that routed
        # any: the busiest expert's tokens over the mean, and the distinct
        # experts touched (mean over the MoE layers)
        self.moe_routed_tokens = 0
        # held experts: assignments to experts on other ranks, which this
        # chip does not compute (``moe_routed_tokens`` then counts the
        # assignments to the experts held here)
        self.moe_away_tokens = 0
        self.moe_expert_load_max_ratio = 0.0
        self.moe_experts_touched = 0.0
        # of the same step, the dropless grouped product's rows routed
        # over rows walked (mean over the MoE layers), and which product
        # runs: "pallas" (ops/grouped_matmul.py) or "xla" (lax.ragged_dot)
        self.moe_tile_fill_ratio = 0.0
        self.grouped_backend = None
        if model_cfg.num_experts and model_cfg.expert_capacity_factor <= 0:
            from helix_tpu.models.moe import grouped_backend

            E, F = model_cfg.hidden_size, model_cfg.expert_width
            self.grouped_backend = grouped_backend(
                [(E, F), (F, E)], self._backend)
        # the query block of the state segment's attention call, from the
        # segment's static width as both ragged kernels read it: 1 token
        # for plain decode, 8 under speculation
        from helix_tpu.ops.paged_kernel import query_block

        self.attn_q_block = query_block(self._spec_width())
        # ... and of the PREFILL segment's paged call in the last launch that
        # had one (the page kind's ``query_block``; 0 before any)
        self.chunk_q_block = 0
        # the step in progress, by named phase (obs.trace.phase): the
        # engine loop clears it at the top of a pass and files it in the
        # flight record; standalone step() callers never read it
        self.step_phases = obs_trace.Phases()
        # the PARTS of admit and dispatch (claim, plan, sync_state,
        # launch), a sink of their own so that the phases above read what
        # they read without them; cleared and filed with step_phases
        self.step_parts = obs_trace.Phases()

    def _part(self, name: str, **attrs) -> obs_trace.phase:
        """A span that is one of the parts of this step's admit and
        dispatch: ``with self._part("helix.loop.claim"):``."""
        return obs_trace.phase(name, into=self.step_parts, **attrs)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    @property
    def kv_filestore(self):
        return self._kv_filestore

    @kv_filestore.setter
    def kv_filestore(self, store) -> None:
        # wired after construction (node_agent): refused there and then
        if store is not None:
            _refuse_call(self.model_cfg, "the persistent KV filestore")
        self._kv_filestore = store

    def _live_positions(self, draft_len=None):
        """The positions of the running rows (of a launch: those its
        ``draft_len`` does not sit out), from the host's mirrors."""
        live = np.asarray(self._active_sent) > 0
        if draft_len is not None:
            live &= np.asarray(draft_len) >= 0
        return self._positions[live].astype(np.int64)

    def _note_kinds(self, rows, pos, n_extra, rung, max_rows) -> dict:
        """Add the launch's account to ``mixer_counts``, kind by kind (its
        prefill ``rows``, laid in a bucket of ``rung`` tokens and ``max_rows``
        rows, and the live decode rows at ``pos``); returns what the launch's
        span shows of it (the records' ``launch`` attributes): the launch's
        own increments and the levels."""
        cfg, attrs, levels = self.model_cfg, {}, self.mixer_gauges()
        for kind in self.kinds:
            inc = {}
            if kind.account is not None:
                # (a page kind's kernels size their blocks from the bucket)
                bucket = () if kind is self.mixer else (rung, max_rows)
                inc = kind.account(
                    cfg, self.cache_cfg, rows, pos, n_extra, *bucket)
                for key, n in inc.items():
                    self.mixer_counts[key] += n
            shown = {**inc, "layers": cfg.num_state_layers, **levels}
            attrs.update({attr: shown[key] for attr, key in kind.launch})
        return attrs

    def mixer_values(self) -> dict:
        """What the kinds' series read, by the records' keys: the counts as
        they stand, the state kind's layers and the pools' bytes (no mirror
        is read: any thread may ask)."""
        return {**self.mixer_counts,
                "layers": self.model_cfg.num_state_layers,
                "pool_bytes": self.recurrent_state_bytes,
                **self._pool_bytes}

    def mixer_gauges(self) -> dict:
        """The kinds' levels from the host's mirrors, which the engine's
        thread alone reads: for a launch's span and the flight record."""
        out = {}
        for kind in self.kinds:
            if kind.gauges is not None:
                out.update(kind.gauges(
                    self.model_cfg, self._live_positions()))
        return out

    @property
    def kv_pages_used(self) -> int:
        """Occupied pages in the pool (prefix-cache-owned pages count as
        used: they hold live KV).  Page 0 (garbage) is excluded from
        both sides, so used/capacity is a true occupancy ratio."""
        return self.allocator.used_pages

    @property
    def kv_pages_capacity(self) -> int:
        return max(1, self.cache_cfg.num_pages - 1)

    @property
    def _resident_context_cap(self) -> int:
        """Context limit for a fully device-resident sequence: the
        profile's max_model_len capped by per-sequence page capacity AND
        the physical pool size (a prompt that can never allocate must be
        rejected, not queued forever)."""
        cap = min(
            self.cache_cfg.max_seq_len,
            (self.cache_cfg.num_pages - 1) * self.cache_cfg.page_size,
        )
        if self.cfg.max_model_len is not None:
            cap = min(cap, self.cfg.max_model_len)
        return cap

    @property
    def max_context_len(self) -> int:
        """Hard prompt+generation limit.  With tiered KV residency on
        (ctx_hot_pages > 0 and a host pool) the physical-pool term drops:
        only the hot tail must fit in HBM, the cold middle streams from
        host RAM — capacity is the per-sequence page-table width (and the
        profile's max_model_len)."""
        if self.cfg.ctx_hot_pages > 0 and self.host_pool is not None:
            cap = self.cache_cfg.max_seq_len
            if self.cfg.max_model_len is not None:
                cap = min(cap, self.cfg.max_model_len)
            return cap
        return self._resident_context_cap

    def validate_request(self, req: Request) -> Optional[str]:
        """Admission pre-check, safe from any thread; None = acceptable."""
        plen = len(req.prompt_tokens)
        if plen + 1 > self.max_context_len:
            return (
                f"prompt ({plen} tokens) exceeds the model context limit "
                f"{self.max_context_len}"
            )
        if (
            self.model_cfg.mrope_sections is not None
            and plen > self.cfg.max_prefill_len
        ):
            # VL prefill is single-shot (image splice shapes); text models
            # prefill arbitrarily long prompts in chunks
            return (
                f"vision prompt ({plen} tokens) exceeds max_prefill_len "
                f"{self.cfg.max_prefill_len}"
            )
        if not req.prompt_tokens:
            return "empty prompt"
        if getattr(req, "adapter", ""):
            if self.adapter_pool is None:
                return (
                    f"adapter '{req.adapter}' requested but this engine "
                    "serves without an adapter pool "
                    "(EngineConfig.adapter_pool_slots)"
                )
            if (
                self.adapter_store is not None
                and not self.adapter_pool.resident(req.adapter)
                and not self.adapter_store.contains(req.adapter)
            ):
                return (
                    f"adapter '{req.adapter}' is not published for "
                    f"model '{self.model_cfg.name}'"
                )
        return None

    def add_request(self, req: Request) -> None:
        err = self.validate_request(req)
        if err:
            raise ValueError(err)
        self._requests[req.id] = req
        self.waiting.append(req)

    def abort(self, req_id: str) -> None:
        req = self._requests.get(req_id)
        if req is None or req.finished:
            return
        self._finish(req, FinishReason.ABORT)

    def get_request(self, req_id: str) -> Optional[Request]:
        """Live view of a submitted request (engine-thread callers: the
        quarantine path in EngineLoop inspects admission recency)."""
        return self._requests.get(req_id)

    def has_work(self) -> bool:
        return (
            bool(self.waiting)
            or bool(self.preempted)
            or any(s is not None for s in self.slots)
        )

    def reap_stuck(self, max_queue_seconds: float = 600.0) -> list:
        """Abort requests stuck in the wait queue beyond a budget (page
        starvation under a long-running batch).  The engine-side analogue of
        the reference's auto-wake-stuck-interactions loop (SURVEY.md §5).
        Returns the aborted requests."""
        now = time.monotonic()
        stuck = [
            r for r in list(self.waiting)
            if now - r.submit_time > max_queue_seconds
        ]
        for r in stuck:
            self._finish(r, FinishReason.ABORT)
        return stuck

    def warmup(self, chunked: bool = True) -> None:
        """Compile the unified ragged step's shape ladder ahead of
        traffic (profile-apply time), so first-token latency excludes
        XLA compilation.  Drives one real tiny request through the
        public path (pages are allocated and freed normally) — that
        alone compiles the decode-only entry point, and with it EVERY
        fused-window size and spec-verify width (both are dynamic
        arguments of the one trace, not shape families) — then walks the
        prefill token-bucket ladder against the garbage page.

        Pre-unification this compiled packed buckets + per-window decode
        scans + verify (width × history × tail) triples + chunk/mixed
        (C × history) pairs; the whole zoo is now O(|token ladder|)
        entry points (a ragged final chunk may still compile one extra
        small single-row shape at request time)."""
        if self.model_cfg.mrope_sections is not None:
            return  # VL prefill shape depends on image buckets; skip
        req = Request(
            id="__warmup__",
            prompt_tokens=[0] * min(4, self.cache_cfg.page_size),
            sampling=SamplingParams(max_tokens=2),
        )
        self.add_request(req)
        while self.has_work():
            self.step()
        self._sync_state()
        ps = self.cache_cfg.page_size
        maxP = self.cache_cfg.max_pages_per_seq
        B = self.cfg.max_decode_batch
        can_chunk = (
            chunked and self.max_context_len > self.cfg.max_prefill_len
        )
        hist_variants = [False]
        if self.prefix_cache is not None or can_chunk:
            # cache-hit waves / chunk continuations attend history
            hist_variants.append(True)

        def drive(rung: int, with_hist: bool, rows: int) -> None:
            # one dummy row filling the rung exactly; its table is all
            # garbage-page zeros, so reads see garbage (discarded) and
            # writes land on page 0 — nothing real advances
            plan = PrefillPlan(ps, maxP, rows)
            plan.add(
                None, np.zeros((maxP,), np.int32),
                ps if with_hist else 0, rung, [0] * rung,
                _host_key(0), SamplingParams(),
            )
            self._ragged_step(
                "warmup", plan=plan, draft_len=self._inert_rows, n_extra=0,
            )

        for rung in self._token_ladder:
            for hh in hist_variants:
                drive(rung, hh, B)
        if can_chunk:
            # the dominant per-chunk shapes: full chunks run single-row
            # at the top rung — the FIRST chunk of a cold long prompt
            # has no history, every later chunk does, and the mixed
            # step shares both traces (the state segment rides along in
            # every entry point)
            drive(self.cfg.max_prefill_len, False, 1)
            drive(self.cfg.max_prefill_len, True, 1)
            # a final chunk's one-row token handle seeds its slot through
            # the same patch the request above compiled for a wave's
            self._dstate = _patch_first_tokens(
                self._dstate, jnp.full((B,), -1, jnp.int32),
                jnp.zeros((1,), jnp.int32),
            )

    def step(self) -> list[tuple[Request, int]]:
        """Admit + prefill waiting requests, then one decode step.

        Long prompts prefill one chunk per engine step, so decode slots
        keep producing tokens while a 32k prompt works through its chunks
        (no head-of-line stall for already-running requests).  When both
        a chunk AND active decode slots are pending, the ragged mixed
        step packs them into ONE device call (``enable_mixed_step``).

        Returns [(request, new_token_id), ...] for tokens produced this step.

        ``step()`` is exactly ``step_complete(step_dispatch())`` — the
        engine loop calls the halves itself so that, while admission is
        blocked, the host phase of step N+1 overlaps the device phase of
        step N (ISSUE 33); with nothing in flight the two orders are the
        same code.
        """
        emitted, pend = self.step_dispatch()
        if pend is not None:
            try:
                # stage the NEXT step's cold chunks while this step's
                # device work is still in flight: the gathers/device_puts
                # are async and enqueue after the dispatched step on the
                # device stream, so H2D traffic overlaps compute
                self.prefetch_cold()
                self.step_complete(pend, emitted)
            except Exception:
                # roll the predicted-state advance back before the
                # failure propagates: quarantine bisection and plan
                # followers retry through this wrapper, and a retry
                # against mirrors claiming (position p+n, last_token at
                # p-1) would silently skip/mis-condition n tokens
                self.discard_pending(pend)
                raise
        return emitted

    def step_dispatch(self) -> tuple[list, Optional[PendingStep]]:
        """The HOST phase of one engine step: admission, plan building,
        metadata upload and the (async) device dispatch.  Returns
        ``(emitted_so_far, pending)`` — ``pending`` carries the device
        handles; nothing here blocks on the device (an admission wave's
        first tokens, and the tokens its running rows decoded in it, stay
        there and ride ``pending``'s fetch) except the
        VL single-shot prefill, an admission with no decodable row
        behind it and, under speculation, a wave in which rows decoded.
        Valid while an earlier step is still in flight: see
        ``pipeline_ready``."""
        emitted: list[tuple[Request, int]] = []
        self.first_launch_time = None
        if self.host_pool is not None:
            # release the HBM gather buffers of spills from EARLIER
            # steps (their async D2H copies have landed by now) —
            # step-entry so every step shape drains, including the
            # early-returning mixed step
            self.host_pool.drain_pending()
        # per-step prefill-admission budget (scheduler feedback loop):
        # refreshed every step; admission charges it in _try_claim
        self._budget_left = self.prefill_budget
        if self._plan_drive is not None:
            # follower: the budget is the leader's decision, not ours
            self._budget_left = self._plan_drive.budget
        elif self._plan_recorder is not None:
            self._plan_recorder.budget = self._budget_left
        with obs_trace.phase("helix.loop.admit", into=self.step_phases):
            self._admit(emitted)
        with obs_trace.phase("helix.loop.dispatch", into=self.step_phases):
            return emitted, self._dispatch_after_admit(emitted)

    def _dispatch_after_admit(self, emitted) -> Optional[PendingStep]:
        """What one step launches once admission is done: the long
        prompt's next chunk alone or packed with the decode rows, a
        speculative verify, or the fused decode window."""
        if self._chunking is not None and self._chunking["req"].finished:
            self._chunking = None    # aborted mid-prefill
        slots = range(len(self.slots))
        if (
            self._chunking is not None
            and self.cfg.enable_mixed_step
            and any(self._row_runs(i) for i in slots)
        ):
            return self._mixed_dispatch()
        if self._chunking is not None:
            self._chunk_dispatch()
        if self.spec is not None and self._pending_waves:
            # the drafter reads the host's sequence, which a row that
            # decoded in a wave lags by that token: it could not draft, and
            # the step would lose its verify.  Speculation reconciles
            # before it dispatches as it is (``pipeline_ready``), so the
            # wave's tokens are fetched here, ahead of the drafting
            self._flush_pending_first(emitted)
        # re-check: a chunk that just completed activates its slot and
        # decodes its second token this same step (pre-mixed behaviour);
        # its deferred first token rides that step's single device_get
        if any(self._row_runs(i) for i in slots):
            # speculate when the drafter has something to verify; any
            # step it doesn't (no n-gram hit, EMA-disabled slots, no
            # headroom) falls straight through to the plain fused window
            pend = None
            if self.spec is not None:
                pend = self._spec_dispatch()
            if pend is None:
                pend = self._decode_dispatch()
            return pend
        # nothing decodable (every admitted row ends on its first token and
        # every running row on its wave token, or a chunk whose request
        # aborted between activation and decode): any deferred token must
        # still land — synchronous flush
        self._flush_pending_first(emitted)
        return None

    def step_complete(self, pend: PendingStep, emitted=None) -> list:
        """The RECONCILE phase: the step's one host fetch plus every
        host-visible effect (emits, stop conditions, slot frees).  The
        async loop calls this AFTER dispatching the next step, so the
        fetch blocks only for the device time the host work did not
        already cover."""
        emitted = [] if emitted is None else emitted
        with obs_trace.phase("helix.loop.reconcile", into=self.step_phases):
            if pend.kind == "decode":
                self._decode_complete(pend, emitted)
            elif pend.kind == "spec":
                self._spec_complete(pend, emitted)
            else:
                self._mixed_complete(pend, emitted)
        return emitted

    def _fetch(self, handles):
        """A step's one ``jax.device_get``: the host blocks here until
        the device has run the step."""
        with obs_trace.phase("helix.loop.fetch", into=self.step_phases), \
                self.device_wait:
            out = jax.device_get(handles)
        self._drain_moe_drops()
        return out

    def pipeline_ready(self) -> bool:
        """True when the NEXT dispatch may run against predicted post-step
        state while a step is still in flight (and may itself stay in
        flight).  What makes that safe, for a finish, an admission wave
        and a mixed step alike:

        - the device stream runs programs in launch order, so a page (or a
          slot of the state pool) freed at a reconcile and claimed by the
          next wave is written by the old owner's overrun first and by
          its new owner after;
        - ``_rebuild_state`` keeps the device's last tokens and positions
          for surviving rows, so no rebuild needs tokens still in flight;
        - ``_pending_out`` charges every dispatched-not-reconciled token
          against a row's ``max_tokens`` and page room: a row the tokens
          in flight exhaust is launched inactive (``_row_runs``), and a
          row that ends on a stop token is found one step late, its
          overrun inside the page room the ``room`` check reserved and
          discarded at the reconcile (``slots[i] is not r or r.finished``).

        What still reconciles first: speculation (a row's advance depends
        on acceptance counts the host has not seen, and the drafter reads
        the host's sequence), parked preemptions (a resume uploads the
        mirrors of a row the host must have reconciled) and tiered rows
        (their demotion gathers order against a reconciled cache handle).
        The engine loop adds the conditions it owns: aborts, imports,
        drain, hand-off, checkpoints, preemption for pressure."""
        return not (self.preempted or self.spec is not None or self._tiered)

    def steady_decode(self) -> bool:
        """Plain fused-decode steady state: nothing queued, no chunked
        prefill, clean slot state and headroom in every running row.  The
        multi-host plan leader looks ahead only here (ISSUE 13's rule): a
        follower replays each plan with nothing in flight, so it sees a
        finish one step before the leader does and would claim another
        slot for the same admission."""
        return not (
            self._state_dirty
            or self.waiting
            or self._chunking is not None
            or self._pending_first
        ) and all(
            self._row_runs(i) for i in range(len(self.slots))
            if self._slot_active(i)
        )

    def admission_blocked(self) -> bool:
        """Nothing more could be admitted before the step that was just
        launched ends: requests still queue after this pass's admission
        (for a slot or for pages), or no slot is free for an arrival."""
        return bool(self.waiting) or all(s is not None for s in self.slots)

    def discard_pending(self, pend: PendingStep) -> None:
        """Forget an in-flight dispatch whose completion failed or will
        never run (step-failure path): host bookkeeping only — every
        slot is marked changed so the next ``_sync_state`` re-uploads
        the mirrors rather than trusting device state the failed step
        may have left behind."""
        if pend.kind in ("decode", "mixed"):
            # roll back the predicted-position advance: the mirror's
            # last_token is still the last RECONCILED token (position
            # p-1), so the retry must re-decode from p — leaving the
            # dispatch-time p+n in place would re-sync a (position,
            # last_token) pair that never existed and silently skip n
            # tokens from the client's stream
            self._roll_back(pend.rows, pend.n)
        # a wave's rows advanced one token at its launch: the step that
        # was to fetch them is gone (and so is the device state behind
        # it), so they roll back as a step's do, those of a wave no step
        # has taken yet among them
        for rows, _sampled in pend.waves + self._take_pending_waves():
            self._roll_back(rows, 1)
        for req, tok, row in pend.pending_first:
            self._uncharge(req, 1)
            if (
                req.finished or req.slot is None
                or req.id in self._pending_first_ids
            ):
                continue
            # the prefill that sampled this deferred first token
            # SUCCEEDED — only the decode completion failed.  Put it
            # back so the retry re-seeds the slot from the handle and
            # still emits token #1; dropping it would condition the
            # retried stream on the placeholder mirror (0) and silently
            # lose the prompt's first sampled token.
            self._defer_first_token(req, tok, row)
        self._state_dirty = True
        self._changed_slots.update(range(len(self.slots)))

    def _check_table_room(self, rows: list, n: int) -> None:
        """Headroom invariant, checked loudly on host: the KV write clamps
        its page-table index, so a row whose position can reach table
        capacity inside the ``n`` steps about to launch would silently
        corrupt offset 0 of its last page instead of failing (ADVICE r3).
        ``_row_runs`` and ``_decode_window`` must make this impossible;
        verify it."""
        table_cap = (
            self.cache_cfg.max_pages_per_seq * self.cache_cfg.page_size
        )
        for i, _r in rows:
            if self._positions[i] + n > table_cap:
                raise RuntimeError(
                    f"decode step overruns page-table capacity: slot {i} "
                    f"at position {self._positions[i]} + {n} steps > "
                    f"{table_cap} — headroom invariant violated"
                )

    def _advance(self, rows: list, n: int) -> None:
        """Predicted-state advance: the DEVICE moves every launched row
        forward ``n`` tokens whether or not the host later discards an
        overrun, so the position mirror advances at the launch — this is
        what lets the loop build step N+1's metadata before step N's
        tokens are on host.  The reconcile only fetches, emits and applies
        stop conditions."""
        for i, r in rows:
            self._positions[i] += n
            self._charge(r, n)

    def _roll_back(self, rows: list, n: int) -> None:
        """Undo ``_advance`` for a launch whose tokens will never be
        read."""
        for i, r in rows:
            if self.slots[i] is r:
                self._positions[i] -= n
            self._uncharge(r, n)

    def _pending_out(self, req: Request) -> int:
        """Tokens this request has in flight (dispatched, not yet
        reconciled), a deferred first token among them — the correction
        every budget/headroom read applies so a predicted dispatch can
        never overrun max_tokens or the allocated pages."""
        return self._inflight_out.get(req.id, 0)

    def _charge(self, req: Request, n: int) -> None:
        """``n`` more of this request's tokens are dispatched and not yet
        reconciled (or sit on the device as a deferred first token)."""
        self._inflight_out[req.id] = self._inflight_out.get(req.id, 0) + n

    def _uncharge(self, req: Request, n: int) -> None:
        """``n`` of them reached the host (they are charged by
        ``len(output_tokens)`` from now on), or their step was discarded:
        the request's account is closed when nothing is left in flight."""
        left = self._inflight_out.get(req.id, 0) - n
        if left > 0:
            self._inflight_out[req.id] = left
        else:
            self._inflight_out.pop(req.id, None)

    def _row_runs(self, i: int) -> bool:
        """Slot ``i`` is launched live: decodable, with headroom left
        (``_headroom``).  A row the tokens in flight exhaust finishes at
        their reconcile; until then it sits inactive on the device
        instead of making the loop wait for that reconcile."""
        return self._slot_active(i) and self._headroom(self.slots[i]) > 0

    def _running_rows(self) -> list:
        """``[(slot, Request)]`` of the rows a launch runs live, snapshotted
        at the launch: what its reconcile (or its roll-back) walks."""
        return [
            (i, r) for i, r in enumerate(self.slots) if self._row_runs(i)
        ]

    def _defer_first_token(self, req: Request, tok, row: int) -> None:
        """Leave a prefill's sampled first token on the device: a
        placeholder in the mirror, a device-side patch at the next
        ``_sync_state``, the emit at the next step's batched fetch."""
        self._last_token[req.slot] = 0
        self._pending_token_patches[req.slot] = (tok, row)
        self._pending_first.append((req, tok, row))
        self._pending_first_ids.add(req.id)
        self._charge(req, 1)

    def _take_pending_first(self) -> list:
        pf, self._pending_first = self._pending_first, []
        self._pending_first_ids.clear()
        return pf

    def _take_pending_waves(self) -> list:
        waves, self._pending_waves = self._pending_waves, []
        return waves

    def _take_deferred(self) -> dict:
        """What the step being launched carries for the launches ahead of
        it, as ``PendingStep`` fields: the deferred first tokens (an
        admission wave's, a final chunk's, any re-queued by a failed
        step) and the tokens of the rows that decoded inside the waves.
        Each must ride THIS step's fetch, or its request would emit a
        later token before it."""
        return dict(pending_first=self._take_pending_first(),
                    waves=self._take_pending_waves())

    def _fetch_deferred(self, p: PendingStep, emitted) -> tuple:
        """A step's one fetch, the deferred tokens it carries included
        (each handle once).  They are emitted ahead of the step's own
        tokens, in the order the device produced them: the first tokens,
        then wave by wave the tokens of the rows that decoded there.
        Returns the fetched ``p.handles``."""
        uniq: dict = {}
        for _req, tok, _row in p.pending_first:
            uniq.setdefault(id(tok), tok)
        n, m = len(p.handles), len(p.handles) + len(uniq)
        fetched = self._fetch(
            p.handles + tuple(uniq.values())
            + tuple(sampled for _rows, sampled in p.waves))
        firsts = dict(zip(uniq, fetched[n:m]))
        for req, tok, row in p.pending_first:
            self._finish_first_emit(
                req, int(firsts[id(tok)][row]), emitted)
        for (rows, _sampled), sampled_np in zip(p.waves, fetched[m:]):
            self._finish_wave_emit(rows, sampled_np, emitted)
        return fetched[:n]

    def _finish_wave_emit(self, rows: list, sampled_np, emitted) -> None:
        """The tokens a wave's running rows decoded in it, after their
        handle was fetched with a later step's."""
        for _i, r in rows:
            self._uncharge(r, 1)
        self._emit_row_tokens(rows, sampled_np[:, 0], emitted)

    def _emit_row_tokens(self, rows: list, toks, emitted) -> None:
        """One launched token a row (``toks [B]``) reaches the host: a row
        whose slot no longer holds the request, or whose request finished
        on an earlier token, discards it (the fused-window overrun
        contract)."""
        for i, r in rows:
            if self.slots[i] is not r or r.finished:
                continue  # finished/evicted mid-flight: discard the overrun
            self._last_token[i] = toks[i]
            self.num_decode_tokens += 1
            self._emit(r, int(toks[i]), emitted)

    def _finish_first_emit(self, req: Request, first_token: int,
                           emitted) -> None:
        """Deferred first-token emit, after its handle was fetched as
        part of the step's batched device_get."""
        self._uncharge(req, 1)
        if req.finished:
            return   # aborted after activation: the token is moot
        req.first_token_time = time.monotonic()
        if req.slot is not None:
            self._last_token[req.slot] = first_token
            # a patch not yet consumed by _sync_state is superseded by
            # the now-accurate mirror (a stale patch after the mirror
            # write would double-count the histogram seed)
            self._pending_token_patches.pop(req.slot, None)
        self._emit(req, first_token, emitted)

    def _flush_pending_first(self, emitted) -> None:
        """Fallback when no same-step decode fetch will carry the
        deferred first tokens (and the tokens of the rows that decoded in
        the waves), or when the drafter must see them first: fetch them
        alone."""
        pf, waves = self._take_pending_first(), self._take_pending_waves()
        if not pf and not waves:
            return
        with obs_trace.phase(
            "helix.loop.prefill_sync", into=self.step_phases
        ), self.device_wait:
            toks, wave_toks = jax.device_get((
                [tok for _req, tok, _row in pf],
                [sampled for _rows, sampled in waves],
            ))
        for (req, _tok, row), t_np in zip(pf, toks):
            self._finish_first_emit(req, int(t_np[row]), emitted)
        for (rows, _sampled), sampled_np in zip(waves, wave_toks):
            self._finish_wave_emit(rows, sampled_np, emitted)
        self._drain_moe_drops()   # the fetch above synced the device

    def _request_key(self, req: Request) -> np.ndarray:
        """Root PRNG key for one request: derived from its seed when given,
        else from the engine stream counter.

        Keys are derived ON HOST (splitmix64 -> two uint32 words used as
        threefry key data).  The previous ``jax.random.split`` chain cost a
        device dispatch + a blocking fetch PER REQUEST, device-idle time in
        key bookkeeping for every admission of a burst.  Any distinct
        uint32 pair is a valid threefry key; determinism contracts hold:
        a seeded request's key depends only on its seed (reproducible
        across engines and batchmates), unseeded requests get the engine
        counter stream.
        """
        if req.sampling.seed is not None:
            return _host_key(_SEED_DOMAIN ^ (req.sampling.seed & _M64))
        self._key_nonce += 1
        return _host_key(self._key_base ^ self._key_nonce)

    def _slot_active(self, i: int) -> bool:
        """Occupied and decodable (not mid-chunked-prefill, not claimed by
        the admission pass under way)."""
        s = self.slots[i]
        if s is None or i in self._admitting:
            return False
        return self._chunking is None or s is not self._chunking["req"]

    def next_token_logits(self) -> jax.Array:
        """``[B, V]`` f32 next-token logits of every decode slot from the
        current state; nothing is sampled, advanced or written.  A
        verification hook: two engines that should agree (a tp mesh against
        one chip) are compared by logits with a tolerance, since greedy
        ids flip on near-ties.  Rows of parked slots are garbage."""
        if self._state_dirty or self._dstate is None:
            self._sync_state()
        if self._peek_fn is None:
            # not a serving step shape: compiled on first use, per engine
            @jax.jit
            def peek(params, cache, state: DecodeState):
                logits, _ = _decode_forward(
                    params, cache, state, cfg=self.model_cfg,
                    backend=self._backend, window=None,
                    use_adapters=self.adapter_pool is not None,
                    mesh=self.mesh,
                )
                return logits[:, 0].astype(jnp.float32)

            self._peek_fn = peek
        return self._peek_fn(self._graft_params(), self.cache, self._dstate)

    def generate(
        self, prompts: Sequence[Sequence[int]], sampling: SamplingParams
    ) -> list[list[int]]:
        """Blocking convenience wrapper (tests)."""
        reqs = [
            Request(
                id=f"gen-{i}",
                prompt_tokens=list(p),
                sampling=sampling,
                stop_token_ids=tuple(self.cfg.eos_token_ids),
            )
            for i, p in enumerate(prompts)
        ]
        for r in reqs:
            self.add_request(r)
        while self.has_work():
            self.step()
        return [r.output_tokens for r in reqs]

    # ------------------------------------------------------------------
    # admission + prefill
    # ------------------------------------------------------------------

    def _prompt_hashes(self, req: Request) -> list:
        """Chain digests for the prompt's shareable full pages, capped at
        (plen-1)//ps: the page holding the LAST prompt token is never
        shared so sampling always has at least one token to prefill."""
        if getattr(req, "_page_hashes", None) is None:
            from helix_tpu.engine.kv_cache import PrefixCache

            ps = self.cache_cfg.page_size
            cap = (len(req.prompt_tokens) - 1) // ps
            req._page_hashes = PrefixCache.page_hashes(
                req.prompt_tokens, ps, cap
            )
        return req._page_hashes

    def _ensure_pages(self, need: int) -> bool:
        """can_allocate, with prefix-cache LRU eviction as the backstop.

        With a host tier, eviction SPILLS instead of destroying: the
        page contents demote to host buffers keyed by the same chain
        digest ``match_len`` looks up, so a later prompt sharing the
        prefix restores them instead of re-prefilling (the effective
        prefix cache grows from HBM-pages to host-budget-pages)."""
        if self.allocator.can_allocate(need):
            return True
        if self.prefix_cache is not None:
            entries = self.prefix_cache.evict_entries(
                need - self.allocator.free_pages
            )
            if entries:
                if self.host_pool is not None:
                    self._spill_prefix_pages(entries)
                self.allocator.give_back([p for _, p in entries])
        return self.allocator.can_allocate(need)

    def _spill_prefix_pages(self, entries: list) -> None:
        """Demote evicted prefix pages (``[(digest, page), ...]``) to the
        host tier.  The gather result is fresh device buffers with their
        D2H copies issued asynchronously inside ``put`` — the engine
        thread never blocks on the transfer.  A page the pool rejects
        (budget, injected alloc_fail) is simply lost, exactly as before
        the tier existed."""
        from helix_tpu.engine.kv_cache import gather_pages

        arrays = gather_pages(self.cache, [p for _, p in entries])
        for (digest, _page), page_arrays in zip(entries, arrays):
            self.host_pool.put(digest, page_arrays)

    # ------------------------------------------------------------------
    # continuous multi-LoRA serving (ISSUE 15)
    # ------------------------------------------------------------------

    def publish_adapter(self, adapter_id: str, lora_params: dict,
                        scaling: float) -> None:
        """Publish a trained LoRA tree for ``model@adapter_id`` serving
        — validated against this model's geometry, admitted to the
        host/filestore residency ladder, servable without restart or
        recompile (the pool shape was compiled at warmup)."""
        from helix_tpu.engine.adapters import pack_lora_tree

        if self.adapter_pool is None or self.adapter_store is None:
            raise ValueError(
                "adapter serving is off for this engine "
                "(EngineConfig.adapter_pool_slots)"
            )
        self.adapter_store.publish(
            pack_lora_tree(adapter_id, lora_params, scaling)
        )

    def _adapter_ready(self, req: Request) -> bool:
        """Can this request's adapter reach an HBM slot THIS step?
        Resident or host-resident = yes; otherwise the async
        filestore->host prefetch is (re-)kicked and admission defers —
        a cold adapter overlaps its load with the queue wait and never
        blocks an engine step."""
        aid = getattr(req, "adapter", "")
        if not aid or self.adapter_pool is None:
            return True
        if self.adapter_pool.resident(aid):
            return True
        if self.adapter_store is None:
            return False
        if self.adapter_store.ready(aid):
            return True
        self.adapter_store.prefetch(aid)
        return False

    def ensure_adapter_resident(self, adapter_id: str) -> bool:
        """Synchronously stage an adapter onto the host rung so the NEXT
        admission/resume can pin it without deferring.  Plan followers
        call this before stepping (the leader only broadcasts a request
        once it actually admitted it, so the adapter must load NOW, not
        via the async prefetch the leader's queue wait amortized)."""
        if not adapter_id or self.adapter_pool is None:
            return not adapter_id
        if self.adapter_pool.resident(adapter_id):
            return True
        if self.adapter_store is None:
            return False
        if self.adapter_store.ready(adapter_id):
            return True
        return self.adapter_store.get(adapter_id) is not None

    def _acquire_adapter(self, req: Request) -> Optional[int]:
        """Pin the request's adapter into an HBM pool slot (idempotent
        per request — one ref held admission -> finish, parked requests
        included, so a serving adapter can never be evicted under its
        rows).  None = not loadable this step (cold, or every slot
        pinned): the caller defers."""
        aid = getattr(req, "adapter", "")
        if not aid:
            return 0
        if self.adapter_pool is None:
            return None
        if req.id in self._adapter_refs:
            return self.adapter_pool.slot_for(aid)
        if self.adapter_store is not None:
            # host-resident specs ONLY: this runs on the engine thread,
            # and a filestore fallback here would be a blocking blob
            # read + checksum stalling every in-flight decode — a cold
            # adapter defers (the caller kicks the async prefetch)
            lookup = self.adapter_store.get_resident
            gen = self.adapter_store.generation(aid)
        else:
            lookup, gen = (lambda _id: None), None
        slot = self.adapter_pool.acquire(aid, lookup, generation=gen)
        if slot is not None:
            self._adapter_refs[req.id] = aid
        return slot

    def _release_adapter(self, req: Request) -> None:
        aid = self._adapter_refs.pop(req.id, None)
        if aid is not None and self.adapter_pool is not None:
            self.adapter_pool.release(aid)

    def _graft_params(self):
        """The model params with the adapter pool's stacked slot arrays
        merged into each targeted layer entry (shallow dict copies —
        the arrays themselves are the pool's).  Cached per pool
        version: loads/evictions swap values, never shapes, so the
        compiled step never retraces on adapter churn."""
        if self.adapter_pool is None:
            return self.params
        cached = self._grafted_params
        if cached is not None and cached[0] == self.adapter_pool.version:
            return cached[1]
        merged = dict(self.params)
        layers = dict(merged["layers"])
        for t, entry in self.adapter_pool.entries().items():
            layers[t] = {**layers[t], **entry}
        merged["layers"] = layers
        self._grafted_params = (self.adapter_pool.version, merged)
        return merged

    def _note_adapter_rows(self, plan, draft_len) -> None:
        """Bank this device call's rows per adapter id (bounded top-K
        accounting on the pool) — host-side dict math only."""
        pool = self.adapter_pool
        if pool is None:
            return
        counts: dict = {}
        if plan is not None:
            for row in plan.rows:
                if row.adapter and row.req is not None:
                    aid = getattr(row.req, "adapter", "")
                    if aid:
                        counts[aid] = counts.get(aid, 0) + 1
        if draft_len is not None:
            dl = np.asarray(draft_len)
            for i, req in enumerate(self.slots):
                if (
                    req is not None
                    and i < len(dl)
                    and dl[i] >= 0
                    and self._slot_active(i)
                    and getattr(req, "adapter", "")
                ):
                    counts[req.adapter] = counts.get(req.adapter, 0) + 1
        if counts:
            pool.note_rows(counts)

    def _try_claim(self, req: Request, use_cache: bool = False):
        """Allocate pages + a slot for one waiting request; returns its
        page table or None when resources are unavailable.

        With ``use_cache`` the longest cached prefix is acquired from the
        prefix cache and stitched in front of freshly allocated pages.
        When the chain continues into the HOST tier, those pages are
        restored into freshly allocated device pages here (their uploads
        were typically prefetched while the request sat queue-blocked,
        so the device_put overlapped the wait) and re-adopted into the
        device prefix cache.  ``req.cached_tokens`` records how many
        prompt tokens are already resident (page-aligned)."""
        free_slots = [i for i, s in enumerate(self.slots) if s is None]
        if not free_slots:
            return None
        adapter_slot = 0
        if getattr(req, "adapter", ""):
            # pin the adapter into an HBM pool slot BEFORE any page/slot
            # mutation — a cold adapter defers the whole claim (the ref,
            # once held, survives queue waits and parks until finish)
            got = self._acquire_adapter(req)
            if got is None:
                return None
            adapter_slot = got
        plen = len(req.prompt_tokens)
        ps = self.cache_cfg.page_size
        maxP = self.cache_cfg.max_pages_per_seq
        limit = min(plen + req.sampling.max_tokens, self.max_context_len)
        need = min(self.allocator.pages_needed(limit, ps), maxP)
        k = 0
        hashes: list = []
        if use_cache and self.prefix_cache is not None:
            hashes = self._prompt_hashes(req)
            k = self.prefix_cache.match_len(hashes)
        stateful = (self.prefix_cache is not None
                    and self.prefix_cache.stateful)
        shortened = False
        if stateful:
            # a hit cut back (maybe to nothing) to a boundary whose conv
            # state is on file: pages alone never resume a sequence
            chain = self._prompt_hashes(req)
            shortened = self.prefix_cache.match_len(chain) < (
                self.prefix_cache.match_len(chain, pages_only=True))
        # tiered KV residency (ISSUE 20): a sequence longer than hot tail
        # + one stream chunk admits with only its FIRST dispatch's pages;
        # _tiered_prep grows the table lazily each step and demotes pages
        # behind the hot tail to the host pool.  ctx_pin rows (context-
        # cache creation prefills) stay fully resident.
        tiered = (
            self.cfg.ctx_hot_pages > 0
            and self.host_pool is not None
            and not getattr(req, "ctx_pin", False)
            and need > k + self.cfg.ctx_hot_pages + self.cfg.ctx_stream_pages
        )
        if (
            self.cfg.ctx_hot_pages > 0
            and self.host_pool is not None
            and not tiered
        ):
            # short (or pinned) rows on a tiered engine stay fully
            # resident, so they must fit the physical pool exactly as on
            # a non-tiered engine
            limit = min(limit, self._resident_context_cap)
            need = min(self.allocator.pages_needed(limit, ps), maxP)
        if tiered:
            # cover exactly the first dispatch: the first prefill chunk
            # for long prompts, else the whole prompt plus one decode
            # token (wave admissions dispatch before any prep pass runs)
            if plen > self.cfg.max_prefill_len:
                first = min(limit, self.cfg.max_prefill_len)
            else:
                first = min(limit, plen + 1)
            need_now = min(
                need, max(k, self.allocator.pages_needed(first, ps))
            )
        else:
            need_now = need
        shared: list = []
        if use_cache and self.prefix_cache is not None:
            if not self._ensure_pages(need_now - k):
                return None   # blocked retry: no acquire, no stat churn
            shared = self.prefix_cache.acquire(hashes[:k])
            if stateful:
                # making room may have evicted the boundary's state
                n = len(shared)
                while n and self.prefix_cache.state_at(
                        hashes[n - 1]) is None:
                    n -= 1
                self.prefix_cache.release(shared[n:])
                shared = shared[:n]
        need_new = need_now - len(shared)
        if not self._ensure_pages(need_new):
            if shared:
                self.prefix_cache.release(shared)
            return None
        slot = free_slots[0]
        pages = shared + self.allocator.allocate(req.id, need_new)
        req.slot = slot
        req.admitted_time = time.monotonic()   # queue wait ends here
        restored = 0
        if use_cache and self.host_pool is not None and hashes:
            restored = self._restore_host_prefix(req, hashes, shared, pages)
        if use_cache and self.kv_filestore is not None and hashes:
            # the persistent rung below the host tier (ISSUE 14): the
            # chain's continuation may survive on the filestore across
            # restarts — verified blobs restore and re-adopt exactly
            # like host pages; a corrupt/missing blob truncates the
            # chain and the remainder prefills (never an error)
            restored += self._restore_filestore_prefix(
                req, hashes, len(shared) + restored, pages
            )
        req.cached_tokens = (len(shared) + restored) * self.cache_cfg.page_size
        if shortened:
            self.prefix_hits_shortened += 1
        if stateful and shared:
            # the sequence resumes from the state filed at the boundary its
            # shared pages end on (a cold row reads zeros: its history is 0)
            self._restore_state(
                slot, self.prefix_cache.state_at(hashes[len(shared) - 1]))
        if self._plan_recorder is not None:
            # leader: this admission is final — broadcast the full
            # request identity plus the cached_tokens the prefix /
            # filestore rungs restored (followers verify, so a
            # leader-local disk hit can never silently desync replay)
            self._plan_recorder.note_admit(req)
        if self._plan_drive is not None:
            want = self._plan_drive.cached_tokens.get(req.id)
            if want is not None and want != req.cached_tokens:
                raise RuntimeError(
                    f"plan-follow divergence: request {req.id} restored "
                    f"{req.cached_tokens} cached prompt tokens locally "
                    f"but the leader's plan recorded {want} — the "
                    "prefix/filestore rungs drifted between hosts "
                    "(point both hosts at the same filestore dir)"
                )
        self.num_admitted += 1
        if self._budget_left is not None:
            # charge the uncached prefill work this admission injects
            self._budget_left -= max(
                1, len(req.prompt_tokens) - req.cached_tokens
            )
        if self.on_admit is not None:
            try:
                self.on_admit(req)
            except Exception:  # noqa: BLE001 — policy hooks never fail admission
                logging.getLogger(__name__).exception(
                    "on_admit hook failed for request %s", req.id
                )
        if self.prefix_cache is not None:
            # request-level outcome: did THIS admission reuse any cached
            # prefix pages?  (page-level pools are record_claim below)
            if shared or restored:
                self.prefix_cache_hits += 1
            else:
                self.prefix_cache_misses += 1
        if use_cache and self.prefix_cache is not None:
            self.prefix_cache.record_claim(
                len(shared) + restored, len(hashes)
            )
        if shared:
            self._shared_pages.setdefault(req.id, []).extend(shared)
        # pages round up to page granularity; the model context limit
        # still binds exactly.  Tiered rows keep the full logical limit —
        # their tables grow lazily, so page count is not a length cap.
        if tiered:
            req.max_len = limit
        else:
            req.max_len = min(len(pages) * ps, self.max_context_len)
        self.slots[slot] = req
        self._slot_adapters[slot] = adapter_slot
        table = np.zeros((maxP,), np.int32)
        table[: len(pages)] = pages
        self._page_tables[slot] = table
        if tiered:
            # lo == hi == cached prefix pages: the restored/shared head
            # is never demoted (prefix-cache shares it), keeping the
            # cold span contiguous past it.  ``table`` is the object
            # prefill plans alias, so lazy growth/demotion lands in
            # already-built plans before finalize_device reads them.
            self._tiered[slot] = {
                "lo": req.cached_tokens // ps,
                "hi": req.cached_tokens // ps,
                "top": len(pages),
                "rid": req.id,
                "table": table,
            }
        return table

    def _restore_state(self, slot: int, state) -> None:
        """Write a filed conv state ``[conv layers, K - 1, E]`` into a
        slot's row of the state pool, ahead of the step that resumes the
        sequence (dispatched in order on the device's stream)."""
        with obs_trace.phase("helix.state.restore", into=self.step_phases):
            self.cache = dataclasses.replace(
                self.cache,
                state=_restore_state_fn(
                    self.cache.state, jnp.int32(slot), state),
            )
            self.num_state_restores += 1

    def _snap_tokens(self, req: Request, start: int, rem: int) -> int:
        """How many of a prefill row's ``rem`` tokens (from ``start``) lie
        before the page boundary whose conv state the step hands back: the
        row's end for a chunk that continues a prompt, else the last
        boundary a later request with this prompt could share up to (the
        page of the last prompt token is never shared).  0: none."""
        if self.prefix_cache is None or not self.prefix_cache.stateful:
            return 0
        ps = self.cache_cfg.page_size
        end, plen = start + rem, len(req.prompt_tokens)
        if end < plen:
            bound = end - end % ps
        else:
            bound = (plen - 1) // ps * ps
        return max(bound - start, 0)

    def _restore_host_prefix(
        self, req: Request, hashes: list, shared: list, pages: list
    ) -> int:
        """Promote the host-resident continuation of the prefix chain
        into this request's freshly allocated device pages.

        Walks digests past the device-matched head, claims each page
        from the host pool (checksum-verified; a corrupt or concurrently
        evicted entry truncates the chain — the remainder prefills
        normally, correct by construction), writes the batch back with
        one donated scatter, and re-adopts the pages into the device
        prefix cache so the NEXT sharer hits in HBM."""
        k = len(shared)
        entries: list = []
        digests: list = []
        # a tiered claim may have allocated fewer pages than the digest
        # chain is long — restore only what has a device target
        while k + len(entries) < min(len(hashes), len(pages)):
            h = hashes[k + len(entries)]
            if not self.host_pool.contains(h):
                break
            e = self.host_pool.take_restored(h)
            self._prefetched.discard(h)   # consumed (or dropped corrupt)
            if e is None:   # corrupt (detected + dropped) — chain ends
                break
            entries.append(e)
            digests.append(h)
        if not entries:
            return 0
        from helix_tpu.engine.kv_cache import restore_pages

        t0 = time.monotonic()
        targets = pages[k:k + len(entries)]
        self.cache = restore_pages(self.cache, targets, entries)
        self.restore_seconds += time.monotonic() - t0
        if self.prefix_cache is not None:
            adopted = self.prefix_cache.adopt(digests, targets)
            if adopted:
                # same ownership transfer as _adopt_prompt_pages: the
                # cache owns them, the request holds one ref until finish
                self.allocator.detach(req.id, adopted)
                self._shared_pages.setdefault(req.id, []).extend(adopted)
        return len(entries)

    def _cached_prefix_pages(self, req: Request) -> int:
        """Resident prefix length in pages across the tiers this engine
        can restore from (device chain, its host-spilled continuation,
        then the persistent filestore rung) — the admission router's
        signal that a prompt's remainder must attend history."""
        if self.prefix_cache is None:
            return 0
        hashes = self._prompt_hashes(req)
        k = self.prefix_cache.match_len(hashes)
        if self.host_pool is not None:
            while k < len(hashes) and self.host_pool.contains(hashes[k]):
                k += 1
        if self.kv_filestore is not None:
            while k < len(hashes) and self.kv_filestore.contains(
                hashes[k]
            ):
                k += 1
        return k

    def _restore_filestore_prefix(
        self, req: Request, hashes: list, k: int, pages: list
    ) -> int:
        """Promote the filestore-resident continuation of the prefix
        chain (digests past position ``k``) into this request's freshly
        allocated device pages — the cross-restart sibling of
        ``_restore_host_prefix``.  Every blob is checksum-verified by
        ``KVFilestore.get`` BEFORE anything touches the pool; a missing
        or corrupt blob truncates the chain (typed counter) and the
        remainder prefills normally.  Restored pages re-adopt into the
        device prefix cache so the NEXT sharer hits in HBM."""
        entries: list = []
        digests: list = []
        while (
            k + len(entries) < len(hashes)
            and k + len(entries) < len(pages)
        ):
            e = self.kv_filestore.get(hashes[k + len(entries)])
            if e is None:   # miss or corrupt — chain ends, recompute
                break
            entries.append(e)
            digests.append(hashes[k + len(entries) - 1])
        if not entries:
            return 0
        from helix_tpu.engine.kv_cache import restore_pages

        t0 = time.monotonic()
        targets = pages[k:k + len(entries)]
        self.cache = restore_pages(self.cache, targets, entries)
        self.restore_seconds += time.monotonic() - t0
        self.filestore_restored_pages += len(entries)
        if self.prefix_cache is not None:
            adopted = self.prefix_cache.adopt(digests, targets)
            if adopted:
                self.allocator.detach(req.id, adopted)
                self._shared_pages.setdefault(req.id, []).extend(adopted)
        return len(entries)

    def _prefetch_host_prefix(self, req: Request) -> None:
        """Start host->device uploads for the waiting head's host-resident
        prefix pages while it is still resource-blocked: ``device_put``
        is async, so the transfer rides the queue wait (the same
        host/device overlap recipe as spec drafting) and the eventual
        ``_restore_host_prefix`` consumes in-flight handles instead of
        paying the upload at admission time.

        Device handles are bounded to ONE in-flight chain: a new wave
        (different waiting head) releases the previous wave's uploads —
        prefetch borrows HBM from a machine that is out of it, so
        handles whose admission never happened (request shed, chain
        superseded) must not linger until LRU eviction."""
        if self.host_pool is None or self.prefix_cache is None:
            return
        hashes = self._prompt_hashes(req)
        k = self.prefix_cache.match_len(hashes)
        chain = []
        while k < len(hashes) and self.host_pool.contains(hashes[k]):
            chain.append(hashes[k])
            k += 1
        for stale in self._prefetched - set(chain):
            self.host_pool.release_device(stale)
        self._prefetched = set()
        for h in chain:
            if not self.host_pool.prefetch(h):
                break
            self._prefetched.add(h)

    def _admit(self, emitted) -> None:
        # Long prompts that cannot start THIS step (another chunked prefill
        # already in flight) are set aside rather than blocking the queue:
        # short prompts behind them still admit while decode keeps running.
        # They go back at the queue head afterwards, so FIFO order among
        # long prompts is preserved.  Resource exhaustion (no slot/pages)
        # still blocks FIFO — bypassing there would let a stream of short
        # prompts starve a long prompt of the very pages it is waiting for.
        if any(r.finished for r in self.waiting):
            # purge aborted-while-queued requests ANYWHERE in the queue,
            # not just at the head: a finished request deep in the list
            # would otherwise keep counting against queue-depth/token
            # bounds (and the scheduler's per-tenant queues) until
            # admission happened to reach it
            self.waiting[:] = [r for r in self.waiting if not r.finished]
        deferred: list[Request] = []
        pending: list = []   # (batch, first_tokens device handle) per call
        try:
            self._admit_inner(emitted, deferred, pending)
        finally:
            if pending:
                with self._part("helix.loop.claim"):
                    self._finish_packed_admissions(pending)
            self._admitting.clear()
            if deferred:
                self.waiting[:0] = deferred
        if self.preempted:
            # swapped-out decoders resume AFTER the wait queue got its
            # chance at the freed pages (they were preempted FOR that
            # queue — resume-first would re-grab the pages and starve it);
            # the loop's admission deadline backstops a park that never
            # clears
            self._try_resume()
        if not self.waiting and self._prefetched:
            # the queue unblocked without consuming the prefetched chain
            # (head admitted fresh, shed, or aborted): let its device
            # uploads go — no future wave would release them otherwise
            for h in self._prefetched:
                self.host_pool.release_device(h)
            self._prefetched = set()

    def _admit_inner(self, emitted, deferred: list, pending: list) -> None:
        while self.waiting:
            if (
                self._budget_left is not None
                and self._budget_left <= 0
            ):
                # per-step prefill-admission budget spent (scheduler
                # TTFT-burn feedback): stop admitting; decode keeps
                # running and the next step gets a fresh budget.  The
                # budget starts >= 1, so the first admission of a step
                # always proceeds — a shrunken budget throttles, it can
                # never wedge admission.
                return
            if self.waiting[0].finished:   # aborted while queued
                self.waiting.pop(0)
                continue
            req = self.waiting[0]
            if not self._adapter_ready(req):
                # cold adapter: its filestore->host prefetch was just
                # (re-)kicked — set the request aside like a blocked
                # long prompt so everything behind it keeps admitting
                # and the engine step never waits on the load
                deferred.append(self.waiting.pop(0))
                continue
            plen = len(req.prompt_tokens)
            needs_chunking = plen > self.cfg.max_prefill_len
            is_mrope = self.model_cfg.mrope_sections is not None
            if not needs_chunking and not is_mrope:
                # short text prompts — cold AND prefix-cache hits — pack
                # into ONE ragged prefill segment (a hit row's remainder
                # attends the shared pages via its per-row history
                # length; pre-unification each hit paid its own padded
                # chunk call).  First tokens stay on the device and ride
                # the next decode step's fetch.
                if not self._admit_wave(pending):
                    # resource wait: overlap it with the host->device
                    # uploads the eventual claim will consume
                    self._prefetch_host_prefix(req)
                    return
                continue
            if needs_chunking and self._chunking is not None:
                # one chunked prefill in flight at a time — set this long
                # prompt aside so the shorts behind it are not head-of-line
                # blocked (VERDICT r2 weak #6)
                deferred.append(self.waiting.pop(0))
                continue
            with self._part("helix.loop.claim"):
                table = self._try_claim(req, use_cache=not is_mrope)
            if table is None:
                if not is_mrope:
                    self._prefetch_host_prefix(req)
                return  # resource wait; decode will free pages
            self.waiting.pop(0)
            slot = req.slot
            if needs_chunking:
                # defer to _chunk_dispatch: one chunk per engine step, decode
                # interleaves; the slot stays inactive until the prompt is
                # fully cached.  A prefix-cache hit starts past the
                # resident pages: those tokens are never prefilled again.
                self._chunking = {
                    "req": req, "table": table, "next": req.cached_tokens,
                    "key": self._request_key(req), "slot": slot,
                }
                self._state_dirty = True
                self._changed_slots.add(slot)
                continue
            first_token = self._prefill(req, table, slot=slot)
            req.first_token_time = time.monotonic()
            self._positions[slot] = plen
            self._mrope_delta[slot] = req.mrope_delta
            self._last_token[slot] = first_token
            self._state_dirty = True
            self._changed_slots.add(slot)
            self._emit(req, int(first_token), emitted)

    def _admit_wave(self, pending: list) -> int:
        """Claim as many waiting short text prompts as fit one ragged
        prefill segment and prefill them in ONE unified step.  Cold
        prompts and prefix-cache hits pack the same flat token axis — a
        hit row's remainder attends the shared pages through its per-row
        history length, so hit bursts no longer serialize through padded
        one-request chunk calls.  Returns requests admitted (0 =
        blocked on resources).

        First tokens are NOT fetched: the device handle is appended to
        ``pending`` for ``_finish_packed_admissions``."""
        C_cap = self.cfg.max_prefill_len
        ps = self.cache_cfg.page_size
        maxP = self.cache_cfg.max_pages_per_seq
        B = self.cfg.max_decode_batch
        # capacity-dispatch MoE: one request per call — expert capacity
        # is a shared field across the whole segment, so co-packed
        # requests would perturb each other's routing (and the KV the
        # prefix cache adopts).  The admission loop still issues the
        # calls in one wave with one batched token fetch.  A dropless
        # expert layer has no shared field and packs like a dense model.
        capacity_moe = (self.model_cfg.num_experts > 0
                        and self.model_cfg.expert_capacity_factor > 0)
        max_pack = 1 if capacity_moe else B
        sp_ring = _mesh_sp(self.mesh) > 1
        plan = PrefillPlan(ps, maxP, B)
        batch: list = []
        waves: list = []   # closed (plan, batch) pairs

        def flush():
            nonlocal plan, batch
            if batch:
                waves.append((plan, batch))
            plan = PrefillPlan(ps, maxP, B)
            batch = []

        admitted_any = False
        adapter_deferred: list = []
        while self.waiting:
            req = self.waiting[0]
            if req.finished:
                self.waiting.pop(0)
                continue
            if not self._adapter_ready(req):
                # cold adapter mid-wave: defer (prefetch already
                # kicked), keep packing the rest of the queue
                adapter_deferred.append(self.waiting.pop(0))
                continue
            plen = len(req.prompt_tokens)
            if plen > C_cap:
                break   # long prompt: the outer admission loop chunks it
            if len(batch) >= max_pack:
                flush()
            if (
                (batch or waves or admitted_any)
                and self._budget_left is not None
                and self._budget_left <= 0
            ):
                # budget spent mid-wave: close with what fit (the first
                # claim of a step is always admitted)
                break
            cache_match = 0
            with self._part("helix.loop.claim"):
                if self.prefix_cache is not None:
                    cache_match = self._cached_prefix_pages(req)
                if sp_ring and batch and (cache_match or plan.has_hist):
                    # ring attention has no segment ids: a history-
                    # attending row runs alone in its call on sp meshes
                    flush()
                table = self._try_claim(req, use_cache=cache_match > 0)
            if table is None:
                break
            self.waiting.pop(0)
            self._admitting.add(req.slot)
            admitted_any = True
            start = req.cached_tokens   # 0 unless prefix-cache hit
            rem = plen - start
            with self._part("helix.loop.plan"):
                if batch and not plan.fits(rem, C_cap):
                    flush()
                carry, sub = _host_split(self._request_key(req))
                self._slot_keys[req.slot] = carry
                plan.add(
                    req, table, start, rem,
                    req.prompt_tokens[start:plen], sub, req.sampling,
                    adapter=int(self._slot_adapters[req.slot]),
                    slot=req.slot, snap=self._snap_tokens(req, start, rem),
                )
                batch.append((req, table))
        if adapter_deferred:
            # back at the queue head: FIFO among deferred adapters is
            # preserved and the next admission pass re-checks readiness
            self.waiting[:0] = adapter_deferred
        flush()
        admitted = 0
        for wave_plan, wave_batch in waves:
            # the running rows decode one token in the wave's pass (they
            # are on its token axis either way): re-read per wave, so that
            # headroom and ``max_tokens`` hold across a pass of several
            rows = self._wave_rows()
            draft_len = self._inert_rows
            if rows:
                self._check_table_room(rows, 1)
                draft_len = self._inert_rows.copy()
                draft_len[[i for i, _r in rows]] = 0
            first_tokens, sampled, _, _ = self._ragged_step(
                "admit", plan=wave_plan, draft_len=draft_len, n_extra=0,
            )
            pending.append((wave_batch, first_tokens))
            admitted += len(wave_batch)
            if rows:
                self.num_decode_device_steps += 1
                self.num_wave_decode_tokens += len(rows)
                self._advance(rows, 1)
                self._pending_waves.append((rows, sampled))
        return admitted

    def _wave_rows(self) -> list:
        """``[(slot, Request)]`` an admission wave launches live: every
        row that runs (``_row_runs``: decodable, headroom left after the
        tokens in flight; a slot this pass claimed is neither yet), but
        one whose LAST token this would be while earlier tokens of it are
        in flight (an earlier wave's among them): were that so of every
        row, no step would follow the wave, and ``_flush_pending_first``
        would hand the host the wave's token ahead of the unreconciled
        step's.  Such a row decodes in the step behind the wave instead,
        which reconciles in order.  A plan
        follower replays the leader's sets and never derives its own (with
        nothing in flight it would see another); a wave the plan has no
        set for prefills admissions carried over from a plan the leader
        discarded, whose rows it rolled back: every row sits it out."""
        if self._plan_drive is not None:
            if not self._plan_drive.wave_rows:
                return []
            rows = [(i, self.slots[i])
                    for i in self._plan_drive.wave_rows.pop(0)]
            if not all(self._row_runs(i) for i, _r in rows):
                raise RuntimeError(
                    "plan-follow divergence: the leader's wave decoded "
                    f"slots {[i for i, _r in rows]}, of which "
                    f"{[i for i, _r in rows if not self._row_runs(i)]} "
                    "do not run on this replica"
                )
            return rows
        rows = [
            (i, r) for i, r in self._running_rows()
            if self._headroom(r) > 1 or not self._pending_out(r)
        ]
        if self._plan_recorder is not None:
            self._plan_recorder.wave_rows.append([i for i, _r in rows])
        return rows

    def _finish_packed_admissions(self, pending: list) -> None:
        """Per-request bookkeeping of the admission waves just launched.
        Their first tokens are NOT fetched: they stay on the device, seed
        the new rows there (``_defer_first_token``) and reach the host
        with the tokens of the decode step launched behind the waves."""
        for batch, first_tokens in pending:
            for row, (req, _table) in enumerate(batch):
                slot = req.slot
                self._positions[slot] = len(req.prompt_tokens)
                self._mrope_delta[slot] = 0
                self._state_dirty = True
                self._changed_slots.add(slot)
                self.num_prefill_tokens += (
                    len(req.prompt_tokens) - req.cached_tokens
                )
                self._adopt_prompt_pages(
                    req, self._page_tables[slot]
                )
                self._defer_first_token(req, first_tokens, row)

    def _drain_moe_drops(self) -> None:
        """Fold the queued MoE step stats (``[dropped, routed, load max
        ratio, experts touched, tile fill, away]`` a step, queued un-fetched by
        ``_ragged_step``) into the host counters.  Only arrays the device
        has already produced are read, so a step still in flight is never
        waited for; called after each step's own fetch, which is when its
        stats are ready.  Engine-thread only: the /metrics scrape thread
        reads the plain numbers."""
        ready = [h for h in self._moe_drop_handles if h.is_ready()]
        if not ready:
            return
        self._moe_drop_handles = [
            h for h in self._moe_drop_handles if not h.is_ready()
        ]
        stats = np.asarray(jax.device_get(ready), np.float64)   # [n, 6]
        self.moe_routed_tokens += int(stats[:, 1].sum())
        self.moe_away_tokens += int(stats[:, 5].sum())
        routed = stats[stats[:, 1] > 0]
        if len(routed):
            self.moe_expert_load_max_ratio = float(routed[-1, 2])
            self.moe_experts_touched = float(routed[-1, 3])
            self.moe_tile_fill_ratio = float(routed[-1, 4])
        n = int(stats[:, 0].sum())
        if n <= 0:
            return
        self._moe_dropped += n
        # surfaced instead of silently riding the residual stream
        # (ADVICE r5)
        logging.getLogger(__name__).info(
            "moe prefill dropped %d routing assignments to capacity "
            "overflow (engine total %d)", n, self._moe_dropped,
        )

    @property
    def moe_dropped_tokens(self) -> int:
        """Total MoE prefill routing assignments dropped to expert-
        capacity overflow.  Lock-free plain-int read (GIL-atomic), safe
        from the metrics thread; at most one un-drained prefill wave
        behind the device."""
        return self._moe_dropped

    def _chunk_plan(self, st) -> tuple:
        """ONE ragged row for the in-flight long prefill's next chunk:
        the row's history length is simply the chunk start (no history
        bucketing — the ragged op walks exactly the pages in use), so
        chunked prefill compiles one single-row shape per token-bucket
        rung instead of one per (chunk, history) pair."""
        req: Request = st["req"]
        plen = len(req.prompt_tokens)
        start = st["next"]
        end = min(start + self.cfg.max_prefill_len, plen)
        rem = end - start
        st["key"], sub = _host_split(st["key"])
        plan = PrefillPlan(
            self.cache_cfg.page_size, self.cache_cfg.max_pages_per_seq, 1
        )
        plan.add(
            req, st["table"], start, rem,
            req.prompt_tokens[start:end], sub, req.sampling,
            adapter=int(self._slot_adapters[st["slot"]]),
            slot=st["slot"], snap=self._snap_tokens(req, start, rem),
        )
        return plan, rem, end

    def _finish_chunk(self, st, first_token) -> None:
        """Prompt fully cached: activate the slot (shared by the
        standalone chunk step and the mixed step).  ``first_token`` is the
        final chunk's [R] DEVICE handle and its fetch DEFERS: _sync_state
        seeds the slot's device state from the handle and the emit joins
        a decode step's fetch, so a long-prompt chunk cascade costs one
        host round trip per step, not two."""
        req: Request = st["req"]
        with self._part("helix.loop.claim"):
            self._adopt_prompt_pages(req, st["table"])
        slot = st["slot"]
        self._chunking = None
        self._positions[slot] = len(req.prompt_tokens)
        self._mrope_delta[slot] = req.mrope_delta
        self._slot_keys[slot] = _host_split(st["key"])[0]
        self._state_dirty = True
        self._changed_slots.add(slot)
        self._defer_first_token(req, first_token, 0)

    # per-request cap on prefill_chunk spans: a 128k prompt would
    # otherwise flood its own trace's span budget and evict the decode/
    # emit summary spans recorded later (the spans a slow-request
    # investigation actually needs)
    _MAX_CHUNK_SPANS = 32

    def _should_trace_chunk(self, st: dict, req: Request, end: int) -> bool:
        """First _MAX_CHUNK_SPANS chunks + always the final chunk."""
        n = st.get("chunk_spans", 0)
        if n < self._MAX_CHUNK_SPANS or end >= len(req.prompt_tokens):
            st["chunk_spans"] = n + 1
            return True
        return False

    def _chunk_dispatch(self) -> None:
        """Dispatch ONE chunk of the in-flight long prefill (called once
        per engine step so decode interleaves).  Pure dispatch: non-final
        chunks fetch nothing at all, and the final chunk's first token
        defers into the same-step decode fetch (``_finish_chunk``)."""
        st = self._chunking
        req: Request = st["req"]
        if req.finished:   # aborted mid-prefill
            self._chunking = None
            return
        t0 = time.monotonic()
        with self._part("helix.loop.plan"):
            plan, rem, end = self._chunk_plan(st)
        token, _, _, _ = self._ragged_step(
            "chunk", plan=plan, draft_len=self._inert_rows, n_extra=0,
        )
        self.num_prefill_tokens += rem
        st["next"] = end
        if req.trace_id and self._should_trace_chunk(st, req, end):
            # host-side step attribution (device work is async; the final
            # chunk's span absorbs the sync when the first token is read)
            obs_trace.default_store().record(
                req.trace_id, "prefill_chunk", t0, time.monotonic(),
                plane="engine", request_id=req.id,
                chunk_end=end, tokens=rem,
            )
        if end < len(req.prompt_tokens):
            return
        self._finish_chunk(st, token)

    def _mixed_dispatch(self) -> Optional[PendingStep]:
        """Ragged mixed step: ONE device call advances every running decode
        slot one token AND the in-flight long prefill one chunk — decode
        never stalls (and never pays a second dispatch) while a long
        prompt is being admitted.  Like the decode window it is dispatched
        on predicted state: positions advance here, the chunk's progress
        is the host's own, and a final chunk's token stays on the device
        (``_finish_chunk``) and rides this step's fetch."""
        st = self._chunking
        req: Request = st["req"]
        rows = self._running_rows()
        self._check_table_room(rows, 1)
        t0 = time.monotonic()
        with self._part("helix.loop.plan"):
            plan, rem, end = self._chunk_plan(st)
        token, sampled, _, _ = self._ragged_step(
            "mixed", plan=plan, draft_len=self._zero_rows, n_extra=0,
        )
        self.num_mixed_steps += 1
        self.num_decode_device_steps += 1
        self.num_prefill_tokens += rem
        st["next"] = end
        self._advance(rows, 1)
        if req.trace_id and self._should_trace_chunk(st, req, end):
            obs_trace.default_store().record(
                req.trace_id, "prefill_chunk", t0, time.monotonic(),
                plane="engine", request_id=req.id,
                chunk_end=end, tokens=rem, mixed=True,
            )
        if end >= len(req.prompt_tokens):
            self._finish_chunk(st, token)
        return PendingStep(
            kind="mixed", rows=rows, handles=(sampled,),
            # the final chunk's own token among them
            **self._take_deferred(),
        )

    def _mixed_complete(self, p: PendingStep, emitted) -> None:
        (next_np,) = self._fetch_deferred(p, emitted)
        for _i, r in p.rows:
            self._uncharge(r, 1)
        self._emit_row_tokens(p.rows, next_np[:, 0], emitted)

    def _prefill(
        self, req: Request, page_table: np.ndarray, slot: Optional[int] = None
    ) -> int:
        """VL (mrope) single-shot prefill.  Text prompts never come here:
        short ones pack through ``_admit_wave`` and long ones chunk
        through ``_chunk_dispatch``."""
        assert self.model_cfg.mrope_sections is not None
        plen = len(req.prompt_tokens)
        bucket = _bucket(
            max(plen, self.cache_cfg.page_size),
            self.cache_cfg.page_size,
            self.cfg.max_prefill_len,
        )
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :plen] = req.prompt_tokens
        self._charge_padding(bucket, plen)
        ragged_meta.note_step_shape(
            self._shape_key, ("mrope_prefill", bucket)
        )
        length = np.int32(plen)
        # per-request PRNG stream: seeded requests reproduce exactly
        # regardless of batch-mates; the carry half becomes the slot's
        # device-resident key for decode
        carry, sub = _host_split(self._request_key(req))
        if slot is not None:
            self._slot_keys[slot] = carry
        sampling = SamplingState.from_params([req.sampling])
        embeds = self._splice_embeds(req, tokens, bucket)
        pos3 = np.zeros((3, 1, bucket), np.int32)
        if req.positions3 is not None:
            pos3[:, 0, :plen] = np.asarray(req.positions3)[:, :plen]
        else:
            pos3[:, 0, :plen] = np.arange(plen)[None]
        fn = _build_prefill_fn_mrope(
            self.model_cfg, self.cache_cfg.page_size, self._backend
        )
        self.num_device_calls += 1
        self.cache, token = fn(
            self.params, self.cache, jnp.asarray(tokens), embeds,
            jnp.asarray(pos3), jnp.asarray(page_table)[None],
            jnp.asarray(length), sampling, sub,
        )
        self.num_prefill_tokens += plen
        return int(token[0])

    def _splice_embeds(self, req: Request, tokens: np.ndarray, bucket: int):
        """Embed-lookup the prompt and splice image embeddings in (bucketed
        on the image-token count so VL prefill compiles a handful of shapes)."""
        splice = _build_embed_splice_fn(self.model_cfg)
        E = self.model_cfg.hidden_size
        if req.image_embeds is None:
            img = jnp.zeros((1, E), jnp.dtype(self.model_cfg.dtype))
            pos = jnp.full((1,), bucket + 1, jnp.int32)
            n = jnp.int32(0)
        else:
            n_img = req.image_embeds.shape[0]
            nb = _bucket(max(n_img, 1), 16, 1 << 16)
            img = jnp.zeros((nb, E), jnp.dtype(self.model_cfg.dtype))
            img = img.at[:n_img].set(jnp.asarray(req.image_embeds))
            posn = np.full((nb,), bucket + 1, np.int32)
            posn[:n_img] = req.image_positions
            pos = jnp.asarray(posn)
            n = jnp.int32(n_img)
        return splice(self.params, jnp.asarray(tokens), img, pos, n)

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------

    def _running_mask(self) -> np.ndarray:
        return np.array(
            [1 if self._row_runs(i) else 0 for i in range(len(self.slots))],
            np.int32,
        )

    def _sync_state(self) -> None:
        """One jitted merge uploads the host mirrors of the slots that
        changed; the device-evolving pieces (last tokens, positions, RNG
        keys, penalty histograms) of surviving slots are preserved on
        device, so the merge is valid while a step is in flight."""
        with self._part(
            "helix.loop.sync_state",
            changed_slots=len(self._changed_slots),
            patches=len(self._pending_token_patches),
        ):
            self._upload_state()

    def _upload_state(self) -> None:
        B = self.cfg.max_decode_batch
        V = self.model_cfg.vocab_size
        P = self.cache_cfg.max_pages_per_seq
        active = self._active_sent = self._running_mask()
        sampling = SamplingState.from_params(
            [
                (s.sampling if s is not None else SamplingParams())
                for s in self.slots
            ]
        )
        if self._dstate is None:
            self._dstate = DecodeState(
                last_token=jnp.zeros((B,), jnp.int32),
                positions=jnp.zeros((B,), jnp.int32),
                page_tables=jnp.zeros((B, P), jnp.int32),
                active=jnp.zeros((B,), jnp.int32),
                mrope_delta=jnp.zeros((B,), jnp.int32),
                keys=jnp.zeros((B, 2), jnp.uint32),
                token_counts=jnp.zeros((B, V), jnp.int32),
                adapter_slots=jnp.zeros((B,), jnp.int32),
                sampling=sampling,
            )
        keep = np.array(
            [
                1 if (s is not None and i not in self._changed_slots) else 0
                for i, s in enumerate(self.slots)
            ],
            np.int32,
        )
        # the mirrors go up as COPIES: on a CPU ``jnp.asarray`` shares a
        # numpy array's memory, ``_rebuild_state`` hands these through as
        # they are, and the step that follows is given the state to write
        # in place: it would advance ``self._positions`` itself, under the
        # host's own ``+= 1`` (a TPU copies on upload either way)
        def up(mirror):
            return jnp.asarray(mirror.copy())

        self._dstate = _rebuild_state(
            self._dstate,
            up(self._last_token),
            up(self._positions),
            up(self._page_tables),
            jnp.asarray(active),
            up(self._mrope_delta),
            up(self._slot_keys),
            jnp.asarray(keep),
            up(self._slot_adapters),
            sampling,
        )
        self._changed_slots.clear()
        self._state_dirty = False
        if self._slot_count_overrides:
            # resumed slots: re-inject the saved output-token histogram
            # over the fresh-slot reset the rebuild just applied
            for slot, counts in sorted(self._slot_count_overrides.items()):
                self._dstate = _override_token_counts(
                    self._dstate, jnp.int32(slot), jnp.asarray(counts)
                )
            self._slot_count_overrides.clear()
        if self._pending_token_patches:
            # deferred first tokens: seed the fresh slots' last_token +
            # histogram from the still-on-device handles — the rebuild
            # above used the placeholder mirror (0); one call a handle
            by_handle: dict = {}
            for slot, (tok, row) in self._pending_token_patches.items():
                src = by_handle.setdefault(
                    id(tok), (tok, np.full((B,), -1, np.int32)))[1]
                src[slot] = row
            for tok, src in by_handle.values():
                self._dstate = _patch_first_tokens(
                    self._dstate, jnp.asarray(src), tok
                )
            self._pending_token_patches.clear()

    def _decode_window(self) -> int:
        """Fused decode steps to run before the next host sync.

        Single steps whenever responsiveness or safety needs them:
        pending admissions / an in-flight chunked prefill (they interleave
        per engine step), or any active slot within a window of its token
        budget or page capacity (the device keeps writing KV until the
        window ends, so the window must never overrun either).  Otherwise
        the largest power of two <= decode_steps_per_sync that every
        active slot can absorb (power-of-two bucketing bounds the number
        of compiled variants).
        """
        n_max = self.cfg.decode_steps_per_sync
        if n_max <= 1 or self._chunking is not None or self._cold_active():
            # cold-middle rows stream staged chunks through the primary
            # attention call only — the fused tail re-gathers history
            # without the cold stats, so tiered steps stay single-token
            return 1
        n_active = sum(
            1 for i in range(len(self.slots)) if self._slot_active(i)
        )
        if n_active <= self.cfg.adaptive_sync_max_streams:
            return 1   # interactive: stream per-token
        cap = n_max
        # queue pressure as the device sees it.  A plan follower pins
        # this bit to the leader's value: its own queue drains exactly
        # at each plan boundary, so reading it locally would diverge
        # from the leader's (non-empty) queue and change the fused
        # window — a different compiled shape mid-collective.
        blocked = bool(self.waiting)
        if self._plan_drive is not None:
            blocked = self._plan_drive.queue_blocked
        elif self._plan_recorder is not None:
            self._plan_recorder.queue_blocked = blocked
        if blocked:
            # Admission already ran this step, so a non-empty queue means
            # admission is RESOURCE-blocked — forcing single steps would
            # not admit anything sooner, it would just re-impose the
            # per-token host round trip on the whole running batch (the
            # regression this feature exists to fix).  A short window is
            # still worth it: slots can finish mid-window (EOS), and the
            # host only sees that — and can re-admit — at the window
            # boundary, so cap the queued-work turnover latency at 4
            # steps instead of n_max.
            cap = min(cap, 4)
        for i, req in enumerate(self.slots):
            if not self._row_runs(i):
                continue
            # in-flight tokens (a step not reconciled yet, a deferred first
            # token) count against budget and page room: the predicted
            # dispatch must never overrun what the reconcile will reveal
            cap = min(cap, self._headroom(req))
        if cap <= 1:
            return 1
        n = 1
        while n * 2 <= cap:
            n *= 2
        return n

    def _headroom(self, req: Request) -> int:
        """Tokens left of a row's ``max_tokens`` budget and of its page
        room once the tokens in flight have landed."""
        pend = self._pending_out(req)
        budget = req.sampling.max_tokens - len(req.output_tokens)
        room = (req.max_len or self.cache_cfg.max_seq_len) - req.num_tokens
        return min(budget, room) - pend

    # ------------------------------------------------------------------
    # tiered KV residency: streamed cold-middle attention (ISSUE 20)
    # ------------------------------------------------------------------

    @property
    def kv_cold_pages(self) -> int:
        """Demoted cold-middle pages currently host-resident across all
        tiered slots — the saturation gauge for how much context lives
        past HBM."""
        return sum(
            led["hi"] - led["lo"] for led in self._tiered.values()
        )

    def _cold_active(self) -> bool:
        """True when any tiered slot has a non-empty demoted span (the
        next step must stream cold chunks)."""
        return any(
            led["hi"] > led["lo"] for led in self._tiered.values()
        )

    def _ensure_tiered_pages(self, slot: int, led: dict,
                             upto_tokens: int) -> None:
        """Grow a tiered slot's page table to cover ``upto_tokens``
        written positions.  Fresh pages land at the table's high-water
        mark — both in the ledger's aliased table (already-built plan
        rows see them) and the engine's [B, maxP] mirror."""
        ps = self.cache_cfg.page_size
        maxP = self.cache_cfg.max_pages_per_seq
        need = min(self.allocator.pages_needed(upto_tokens, ps), maxP)
        if need <= led["top"]:
            return
        n_new = need - led["top"]
        if not self._ensure_pages(n_new):
            # demotion runs before growth each step, so the steady-state
            # footprint is hot tail + one growth margin; failing THAT
            # means the pool is undersized for the admitted mix
            raise MemoryError(
                f"tiered slot {slot} cannot grow its page table by "
                f"{n_new} page(s) — device pool exhausted even after "
                "cold demotion"
            )
        pages = self.allocator.allocate(led["rid"], n_new)
        for i, pg in enumerate(pages):
            led["table"][led["top"] + i] = pg
            self._page_tables[slot][led["top"] + i] = pg
        led["top"] = need
        # dirty WITHOUT marking the slot changed: page tables re-upload
        # from the host mirror unconditionally, while the slot's
        # device-evolved PRNG key stream and penalty histogram must
        # survive (a changed-slot rebuild would reset both — seeded
        # sampling would silently replay the key stream)
        self._state_dirty = True

    def _demote_slot(self, slot: int, led: dict, written: int) -> None:
        """Move fully written pages behind the hot tail to the host pool
        (checksummed, pinned) and zero their table entries.  ``written``
        is the number of KV positions already written for this slot —
        only pages wholly below ``written - ctx_hot_pages * page_size``
        demote, so the hot tail always stays device-resident."""
        ps = self.cache_cfg.page_size
        target = min(
            written // ps - self.cfg.ctx_hot_pages,
            self.cache_cfg.max_pages_per_seq,
        )
        if target <= led["hi"]:
            return
        from helix_tpu.engine.kv_cache import gather_pages

        idxs = list(range(led["hi"], target))
        page_ids = [int(led["table"][i]) for i in idxs]
        arrays = gather_pages(self.cache, page_ids)
        for idx, page, page_arrays in zip(idxs, page_ids, arrays):
            # pinned: cold pages are the ONLY copy of mid-history KV —
            # prefix-spill pressure must never evict them
            if not self.host_pool.put(
                ("ctx", led["rid"], idx), page_arrays, pinned=True
            ):
                break   # host budget full: stop demoting, keep resident
            self.allocator.detach(led["rid"], [page])
            self.allocator.give_back([page])
            led["table"][idx] = 0
            self._page_tables[slot][idx] = 0
            led["hi"] = idx + 1
            self.num_ctx_demoted_pages += 1
            # table-only change: see _ensure_tiered_pages — never reset
            # the slot's device key stream / histogram over a demotion
            self._state_dirty = True

    def _tiered_prep(self, n_extra: int) -> None:
        """Per-dispatch residency pass for every tiered slot: demote
        pages that fell behind the hot tail, then grow the table to
        cover this step's writes.  Demote-first frees the pages growth
        is about to claim, bounding the per-slot device footprint at
        hot tail + stream margin."""
        for slot in sorted(self._tiered):
            req = self.slots[slot]
            if req is None:
                continue
            led = self._tiered[slot]
            chunking = (
                self._chunking is not None
                and self._chunking.get("slot") == slot
            )
            if chunking:
                written = int(self._chunking["next"])
                upto = min(
                    len(req.prompt_tokens),
                    written + self.cfg.max_prefill_len,
                )
            else:
                written = int(self._positions[slot])
                upto = written + self._spec_width() + int(n_extra)
            upto = min(
                upto,
                req.max_len or self.cache_cfg.max_seq_len,
                self.cache_cfg.max_seq_len,
            )
            self._demote_slot(slot, led, written)
            self._ensure_tiered_pages(slot, led, upto)

    def _cold_spans(self) -> list:
        """Ordered ``(slot, rid, lo, hi)`` for every tiered slot with a
        non-empty demoted span — the staging order, ascending by slot so
        the chunk-fold merge order is deterministic."""
        spans = []
        for slot in sorted(self._tiered):
            led = self._tiered[slot]
            if led["hi"] > led["lo"] and self.slots[slot] is not None:
                spans.append((slot, led["rid"], led["lo"], led["hi"]))
        return spans

    def _refresh_cold_staged(self) -> Optional[dict]:
        """Assemble (or reuse) the staged cold-chunk slab for the
        current demoted spans: host gathers from the pool (checksum
        verified — a corrupt page raises ``ColdPageError``), packed into
        ``[L, nCb, Ct, KVH, D]`` chunk arrays and ``device_put`` as ONE
        async upload.  Keyed on the exact span set, so ``prefetch_cold``
        can build it while the previous step is still on device and the
        dispatch reuses the in-flight handles."""
        spans = self._cold_spans()
        if not spans:
            self._cold_staged = None
            return None
        key = tuple((rid, lo, hi) for _s, rid, lo, hi in spans)
        staged = self._cold_staged
        if staged is not None and staged["key"] == key:
            return staged
        sp = self.cfg.ctx_stream_pages
        ps = self.cache_cfg.page_size
        groups = []   # (rid, [page entries], valid tokens) per chunk
        for _slot, rid, lo, hi in spans:
            for c0 in range(lo, hi, sp):
                c1 = min(c0 + sp, hi)
                entries = []
                for idx in range(c0, c1):
                    e = self.host_pool.get(("ctx", rid, idx))
                    if e is None:
                        raise ColdPageError(
                            f"cold KV page {idx} of request {rid} "
                            "failed checksum verification on restore — "
                            "refusing to attend corrupt history"
                        )
                    entries.append(e)
                groups.append((rid, entries, (c1 - c0) * ps))
        nC = len(groups)
        nCb = 1
        while nCb < nC:
            nCb *= 2
        e0 = groups[0][1][0]
        L, _ps, KVH, D = np.asarray(e0["k"]).shape
        Ct = sp * ps
        kdt = np.asarray(e0["k"]).dtype
        quant = self.cache_cfg.quantized
        ck = np.zeros((L, nCb, Ct, KVH, D), kdt)
        cv = np.zeros((L, nCb, Ct, KVH, D), kdt)
        lens = np.zeros((nCb,), np.int32)
        cks = np.zeros((L, nCb, Ct, KVH), np.float32) if quant else None
        cvs = np.zeros((L, nCb, Ct, KVH), np.float32) if quant else None
        owners = []
        for j, (rid, entries, n_tok) in enumerate(groups):
            ck[:, j, :n_tok] = np.concatenate(
                [np.asarray(e["k"]) for e in entries], axis=1
            )
            cv[:, j, :n_tok] = np.concatenate(
                [np.asarray(e["v"]) for e in entries], axis=1
            )
            lens[j] = n_tok
            owners.append(rid)
            if quant:
                cks[:, j, :n_tok] = np.concatenate(
                    [np.asarray(e["k_scale"], np.float32)
                     for e in entries], axis=1
                )
                cvs[:, j, :n_tok] = np.concatenate(
                    [np.asarray(e["v_scale"], np.float32)
                     for e in entries], axis=1
                )
        self._cold_staged = {
            "key": key,
            "owners": tuple(owners),
            "lens": lens,
            "nCb": nCb,
            "ct": Ct,
            "k": jax.device_put(ck),
            "v": jax.device_put(cv),
            "ks": None if cks is None else jax.device_put(cks),
            "vs": None if cvs is None else jax.device_put(cvs),
        }
        return self._cold_staged

    def _finalize_cold(self, staged: dict, plan, n_rows: int):
        """Bind the staged slab to THIS dispatch's row axes: per-chunk
        owner rows for the prefill segment (plan row index) and the
        state segment (decode slot), plus each row's demoted token span.
        A chunk whose owner appears in neither axis keeps row -1 and
        masks to zero (admission waves during another row's chunked
        prefill).  Returns ``(cold_arg, cold_chunks, cold_ct)``."""
        nCb = staged["nCb"]
        B = len(self.slots)
        spans = self._cold_spans()
        rid_prow: dict = {}
        if plan is not None:
            for j, r in enumerate(plan.rows):
                if r.req is not None:
                    rid_prow[r.req.id] = j
        ps = self.cache_cfg.page_size
        prow = np.full((nCb,), -1, np.int32)
        srow = np.full((nCb,), -1, np.int32)
        p_lo = np.zeros((max(n_rows, 0),), np.int32)
        p_hi = np.zeros((max(n_rows, 0),), np.int32)
        s_lo = np.zeros((B,), np.int32)
        s_hi = np.zeros((B,), np.int32)
        span_by_rid = {}
        for slot, rid, lo, hi in spans:
            span_by_rid[rid] = (slot, lo, hi)
            j = rid_prow.get(rid)
            if j is not None and j < n_rows:
                p_lo[j] = lo * ps
                p_hi[j] = hi * ps
            if self._slot_active(slot):
                s_lo[slot] = lo * ps
                s_hi[slot] = hi * ps
        for c, rid in enumerate(staged["owners"]):
            got = span_by_rid.get(rid)
            if got is None:
                continue
            slot = got[0]
            j = rid_prow.get(rid)
            if j is not None and j < n_rows:
                prow[c] = j
            if self._slot_active(slot):
                srow[c] = slot
        if not (prow >= 0).any() and not (srow >= 0).any():
            # nothing in THIS dispatch attends cold history (e.g. an
            # admission wave while another row owns every span) — skip
            # the cold argument so the call keeps its legacy trace
            return None
        self.num_ctx_stream_chunks += len(staged["owners"])
        cold_arg = (
            staged["k"], staged["v"], staged["ks"], staged["vs"],
            jnp.asarray(prow), jnp.asarray(srow),
            jnp.asarray(staged["lens"]),
            jnp.asarray(p_lo), jnp.asarray(p_hi),
            jnp.asarray(s_lo), jnp.asarray(s_hi),
        )
        return cold_arg, nCb, staged["ct"]

    def prefetch_cold(self) -> None:
        """Stage the NEXT dispatch's cold chunks while the current step
        is still in flight: demotion gathers and the slab's ``device_put``
        are async — they enqueue after the dispatched step on the device
        stream, so the H2D traffic overlaps its compute and the next
        ``_ragged_step`` finds the handles already uploaded.  Called by
        ``step()`` / the async loop between dispatch and complete."""
        if not self._tiered:
            return
        for slot in sorted(self._tiered):
            req = self.slots[slot]
            if req is None:
                continue
            led = self._tiered[slot]
            if (
                self._chunking is not None
                and self._chunking.get("slot") == slot
            ):
                written = int(self._chunking["next"])
            else:
                written = int(self._positions[slot])
            self._demote_slot(slot, led, written)
        self._refresh_cold_staged()

    # ------------------------------------------------------------------
    # preemption-by-swap (ISSUE 6)
    # ------------------------------------------------------------------

    def preempt(self, req_id: str) -> bool:
        """Swap a running decoder out to host RAM and park it for exact
        resume: private page contents + the device-evolved sampler state
        (PRNG key stream, output-token histogram) move to the host tier,
        the slot and pages free, and the request joins ``preempted``.

        Shared prefix pages stay in the device prefix cache with their
        refcounts held — they are shared (typically the hot system
        prompt), so swapping them would free nothing for anyone else and
        would break other holders' tables.

        Returns False when the request is not preemptible right now
        (no host tier, unknown/finished/queued request, mid-chunk
        prefill) or the host budget cannot take its pages — the caller
        degrades to the next rung of the ladder (shed)."""
        if self.host_pool is None:
            return False
        req = self._requests.get(req_id)
        if req is None or req.finished or req.slot is None:
            return False
        slot = req.slot
        if not self._slot_active(slot):
            return False   # mid-chunked-prefill: nothing decodable to park
        if slot in self._tiered:
            # a tiered row's cold pages already live in the host pool
            # under ("ctx", ...) keys — swap-out would double-spill and
            # resume could not rebuild the demoted table; shed instead
            return False
        # capture the device-evolving sampler state AFTER making the
        # device copy current — bit-exact resume needs the key stream
        # and penalty histogram exactly where the last step left them
        if self._state_dirty or self._dstate is None:
            self._sync_state()
        key = np.asarray(self._dstate.keys[slot])
        counts = np.asarray(self._dstate.token_counts[slot])
        shared = self._shared_pages.get(req_id, [])
        owned = self.allocator.seq_pages(req_id)
        n_pages = len(owned) + len(shared)
        table = np.array(self._page_tables[slot][:n_pages])
        private = set(owned)
        private_pos = [
            i for i in range(n_pages) if int(table[i]) in private
        ]
        from helix_tpu.engine.kv_cache import gather_pages

        page_ids = [int(table[i]) for i in private_pos]
        arrays = gather_pages(self.cache, page_ids) if page_ids else []
        put_keys = []
        for pos, page_arrays in zip(private_pos, arrays):
            k = ("seq", req_id, pos)
            # pinned: prefix-spill pressure must never evict a parked
            # decoder's pages out from under its resume
            if not self.host_pool.put(k, page_arrays, pinned=True):
                for kk in put_keys:   # roll back: preemption is atomic
                    self.host_pool.discard(kk)
                return False
            put_keys.append(k)
        self.preempted.append(
            PreemptedSeq(
                req=req,
                table=table,
                private_pos=private_pos,
                position=int(self._positions[slot]),
                last_token=int(self._last_token[slot]),
                mrope_delta=int(self._mrope_delta[slot]),
                key=key,
                counts=counts,
            )
        )
        if self.allocator.owns(req_id):
            self.allocator.free(req_id)
        self.slots[slot] = None
        req.slot = None
        self._state_dirty = True
        self._changed_slots.add(slot)
        self.num_preemptions += 1
        logging.getLogger(__name__).info(
            "preempted request %s: %d private page(s) swapped to host, "
            "%d shared prefix page(s) kept resident",
            req_id, len(private_pos), len(shared),
        )
        return True

    def preempt_for_pressure(self) -> Optional[str]:
        """Pick and preempt the degradation-ladder victim.

        With a ``victim_policy`` wired (the scheduler's ladder: lowest
        class, then most-over-fair-share tenant, then newest) the
        policy's preference order is walked; otherwise the builtin pick
        applies — the NEWEST admission (least sunk decode work),
        breaking ties toward the largest page footprint (frees the most
        for the starved queue).  Requests already swapped twice are
        exempt — bounded thrash.  Returns the preempted request id, or
        None."""
        cands = [
            (req, i)
            for i, req in enumerate(self.slots)
            if req is not None
            and self._slot_active(i)
            and req.preempt_count < 2
        ]
        if self.victim_policy is not None and cands:
            try:
                ordered = list(
                    self.victim_policy([req for req, _i in cands])
                )
            except Exception:  # noqa: BLE001 — a policy bug degrades, not kills
                logging.getLogger(__name__).exception(
                    "victim_policy failed; falling back to builtin pick"
                )
                ordered = []
            for req in ordered:
                if req.finished or req.slot is None:
                    continue
                if self.preempt(req.id):
                    req.preempt_count += 1
                    return req.id
            if ordered:
                return None   # the policy's candidates all declined
        while cands:
            req, i = max(
                cands,
                key=lambda c: (
                    c[0].admitted_time or 0.0,
                    len(self.allocator.seq_pages(c[0].id))
                    + len(self._shared_pages.get(c[0].id, ())),
                ),
            )
            if self.preempt(req.id):
                req.preempt_count += 1
                return req.id
            cands.remove((req, i))
        return None

    # ------------------------------------------------------------------
    # portable request snapshots (ISSUE 11)
    # ------------------------------------------------------------------

    def _snapshot_pages(self, table, n_pages: int, private_pos=None,
                        req_id: str = "") -> Optional[tuple]:
        """Gather the sequence's pages as host numpy dicts, in table
        order, with their stored-representation checksums.  Pages listed
        in ``private_pos`` are read from the host pool (a parked
        decoder's swapped-out pages — already spilled, verified at get);
        everything else gathers from the device pool.  Returns
        (pages, checksums) or None when a host copy failed verification
        (the caller degrades to shed — never exports wrong KV)."""
        from helix_tpu.engine.kv_cache import gather_pages, page_checksum

        private = set(private_pos or ())
        device_pos = [i for i in range(n_pages) if i not in private]
        gathered = {}
        if device_pos:
            page_ids = [int(table[i]) for i in device_pos]
            arrays = gather_pages(self.cache, page_ids)
            for pos, page_arrays in zip(device_pos, arrays):
                gathered[pos] = {
                    f: (None if a is None else np.asarray(a))
                    for f, a in page_arrays.items()
                }
        for pos in sorted(private):
            host = self.host_pool.get(("seq", req_id, pos))
            if host is None:   # corrupt or evicted: cannot export exactly
                return None
            gathered[pos] = host
        pages = [gathered[i] for i in range(n_pages)]
        checksums = [page_checksum(p).hex() for p in pages]
        return pages, checksums

    def _snapshot_base(self, req: Request) -> dict:
        return {
            "version": SNAPSHOT_VERSION,
            "model": self.model_cfg.name,
            "request_id": req.id,
            "prompt_tokens": [int(t) for t in req.prompt_tokens],
            "output_tokens": [int(t) for t in req.output_tokens],
            "sampling": dataclasses.asdict(req.sampling),
            "stop_token_ids": [int(t) for t in req.stop_token_ids],
            "tenant": req.tenant,
            "trace_id": req.trace_id,
            "sched_class": req.sched_class,
            "adapter": getattr(req, "adapter", ""),
            "max_len": req.max_len,
            "preempt_count": req.preempt_count,
            "page_size": self.cache_cfg.page_size,
            "num_layers": self.model_cfg.num_layers,
            # a latent pool has no head axis: "kv_heads" 0 tells it from
            # a K/V pool, "head_dim" is then the width of its one array's
            # rows (latent + lane-padded rope key)
            "kv_heads": self._snapshot_geometry()[0],
            "head_dim": self._snapshot_geometry()[1],
            "kv_dtype": self.cache_cfg.dtype,
        }

    def _snapshot_geometry(self) -> tuple:
        """``(kv_heads, head_dim)`` as a snapshot states them
        (``CacheConfig.geometry``: a latent pool's says its one array's
        row width, so a snapshot of another layout is refused by name)."""
        return self.cache_cfg.geometry(self.model_cfg)

    def export_request(self, req_id: str) -> Optional[RequestSnapshot]:
        """Build a portable snapshot of one live request (engine thread).

        Three shapes, mirroring where the request is in its life:

        - **decoding in a slot**: full KV export — the device-evolved
          sampler state is captured via the PR 6 preempt recipe (sync
          the device copy, read the slot's key + penalty histogram) and
          every table page gathers to host with a checksum;
        - **parked preempted**: private pages come from the host pool
          (verified), shared prefix pages from the device;
        - **queued / mid-chunk-prefill**: no KV state — the snapshot
          replays from the prompt on the peer (no token was emitted
          yet, so exactly-once delivery holds trivially).

        Returns None for requests that cannot be exported (unknown,
        finished, VL — image embeds are device arrays bound to this
        runner — or a parked page that failed verification).  The caller
        owns the request's local teardown; export itself mutates
        nothing."""
        _refuse_call(self.model_cfg, "export_request")
        req = self._requests.get(req_id)
        if req is None or req.finished:
            return None
        if req.image_embeds is not None or req.positions3 is not None:
            return None   # VL requests pin device-resident image state
        if req.slot is not None and req.slot in self._tiered:
            # tiered rows have demoted pages only this engine's host
            # pool holds — a snapshot gathered from the device table
            # would carry holes; migration of cold-middle rows is out
            # of scope (the caller degrades to shed/replay)
            return None
        base = self._snapshot_base(req)
        parked = next(
            (st for st in self.preempted if st.req is req), None
        )
        if parked is not None:
            if parked.entries is not None:
                # imported-and-not-yet-resumed: the verified pages are
                # already inline (every table position is private)
                from helix_tpu.engine.kv_cache import page_checksum

                pages = list(parked.entries)
                checksums = [page_checksum(p).hex() for p in pages]
            else:
                snapped = self._snapshot_pages(
                    parked.table, len(parked.table),
                    private_pos=parked.private_pos, req_id=req.id,
                )
                if snapped is None:
                    return None
                pages, checksums = snapped
            base["total_pages"] = len(parked.table)
            counts = parked.counts
            state = dict(
                position=int(parked.position),
                last_token=int(parked.last_token),
                mrope_delta=int(parked.mrope_delta),
                key=[int(parked.key[0]), int(parked.key[1])],
            )
        elif req.slot is not None and self._slot_active(req.slot):
            slot = req.slot
            # capture the device-evolving sampler state AFTER making the
            # device copy current — the same bit-exactness rule as
            # ``preempt``: the key stream and penalty histogram must be
            # exactly where the last step left them
            if self._state_dirty or self._dstate is None:
                self._sync_state()
            key = np.asarray(self._dstate.keys[slot])
            counts = np.asarray(self._dstate.token_counts[slot])
            n_alloc = len(self.allocator.seq_pages(req.id)) + len(
                self._shared_pages.get(req.id, ())
            )
            # ship only pages holding WRITTEN KV (token slots
            # 0..num_tokens-2 — the newest token's KV lands during the
            # NEXT step): admission allocated capacity for max_tokens up
            # front, and shipping that mostly-uninitialized tail would
            # scale the wire bytes with the budget, not the progress
            ps = self.cache_cfg.page_size
            n_resident = min(n_alloc, -(-req.num_tokens // ps))
            snapped = self._snapshot_pages(
                self._page_tables[slot], n_resident
            )
            if snapped is None:
                return None
            pages, checksums = snapped
            base["total_pages"] = n_alloc
            state = dict(
                position=int(self._positions[slot]),
                last_token=int(self._last_token[slot]),
                mrope_delta=int(self._mrope_delta[slot]),
                key=[int(key[0]), int(key[1])],
            )
        else:
            # queued, or mid-chunk prefill (partial KV is not worth
            # shipping: no token emitted, replay is exact by definition)
            base["output_tokens"] = []
            pages, checksums, counts = [], [], None
            state = dict(
                position=None, last_token=None, mrope_delta=0, key=None,
            )
        sparse: dict = {}
        if counts is not None:
            nz = np.nonzero(counts)[0]
            sparse = {int(i): int(counts[i]) for i in nz}
        self.num_snapshots_exported += 1
        return RequestSnapshot(
            **base, **state, token_counts=sparse,
            pages=pages, page_checksums=checksums,
        )

    def export_prefill(self, req_id: str) -> Optional[RequestSnapshot]:
        """Disaggregated prefill/decode handoff (ISSUE 14): snapshot a
        request as soon as its prefill has completed — the first token
        is sampled and every prompt page holds written KV — so a
        decode-pool peer can import it (``import_request``'s
        validate-checksums-before-mutation path) and continue the
        generation bit-identically as an ordinary admission wave.

        Ships *before* meaningful decode happens: the caller invokes
        this the moment output tokens exist.  Refuses requests whose
        prefill has not finished (nothing to hand off — the peer
        replaying from the prompt would be cheaper than shipping) and
        requests whose export would carry no KV.  Export itself mutates
        nothing; the caller tears the local request down only after the
        ship is CONFIRMED, so a failed transfer degrades to local
        decode — never a lost request."""
        _refuse_call(self.model_cfg, "export_prefill")
        req = self._requests.get(req_id)
        if req is None or req.finished or not req.output_tokens:
            return None
        snap = self.export_request(req_id)
        if snap is None or not snap.has_kv:
            return None
        self.num_prefill_exports += 1
        return snap

    def import_request(self, snap: RequestSnapshot) -> Request:
        """Re-admit a snapshot on this engine (engine thread).

        Validation is strictly BEFORE mutation: version, KV geometry
        (page size / layers / heads / head dim / storage dtype must
        match — bit-identical continuation is the contract, not
        best-effort), then EVERY page checksum against the stored
        representation.  Only then does the request enter the engine —
        KV-carrying snapshots park on the ``preempted`` list with their
        verified pages INLINE and re-admit through ``_try_resume`` as a
        plain admission wave (exactly the PR 6 local-resume path, so the
        continuation is bit-identical); plain snapshots join the wait
        queue like any fresh request.  Raises ``SnapshotError`` (typed)
        without touching allocator or queue state on any failure."""
        _refuse_call(self.model_cfg, "import_request")
        if snap.version != SNAPSHOT_VERSION:
            raise SnapshotError(
                f"snapshot version {snap.version} != engine version "
                f"{SNAPSHOT_VERSION}",
                code="snapshot_unsupported",
            )
        existing = self._requests.get(snap.request_id)
        if existing is not None and not existing.finished:
            raise SnapshotError(
                f"request {snap.request_id!r} is already live here",
                code="snapshot_duplicate",
            )
        samp = dict(snap.sampling)
        samp["stop"] = tuple(samp.get("stop", ()) or ())
        req = Request(
            id=snap.request_id,
            prompt_tokens=list(snap.prompt_tokens),
            sampling=SamplingParams(**samp),
            stop_token_ids=tuple(snap.stop_token_ids),
            output_tokens=list(snap.output_tokens),
            trace_id=snap.trace_id,
            tenant=snap.tenant,
            sched_class=snap.sched_class,
            adapter=getattr(snap, "adapter", "") or "",
            preempt_count=int(snap.preempt_count),
        )
        err = self.validate_request(req)
        if err:
            raise SnapshotError(err, code="snapshot_invalid")
        if not snap.has_kv:
            if snap.output_tokens:
                raise SnapshotError(
                    "snapshot carries emitted tokens but no KV state — "
                    "it cannot be continued exactly",
                    code="snapshot_corrupt",
                )
            self._requests[req.id] = req
            self.waiting.append(req)
            self.num_snapshots_imported += 1
            return req
        cc = self.cache_cfg
        geometry = (
            ("page_size", snap.page_size, cc.page_size),
            ("num_layers", snap.num_layers, self.model_cfg.num_layers),
            ("kv_heads", snap.kv_heads, self._snapshot_geometry()[0]),
            ("head_dim", snap.head_dim, self._snapshot_geometry()[1]),
            ("kv_dtype", snap.kv_dtype, cc.dtype),
        )
        for field, theirs, ours in geometry:
            if theirs != ours:
                raise SnapshotError(
                    f"KV geometry mismatch on {field}: snapshot has "
                    f"{theirs!r}, this engine has {ours!r}",
                    code="snapshot_incompatible",
                )
        n = len(snap.pages)
        if n != len(snap.page_checksums) or n == 0:
            raise SnapshotError(
                "page/checksum count mismatch", code="snapshot_corrupt"
            )
        n_total = max(n, int(snap.total_pages or n))
        if n_total > cc.max_pages_per_seq or n_total > cc.num_pages - 1:
            raise SnapshotError(
                f"snapshot needs {n_total} pages; this engine caps a "
                f"sequence at "
                f"{min(cc.max_pages_per_seq, cc.num_pages - 1)}",
                code="snapshot_incompatible",
            )
        # the shipped pages must COVER every written KV slot (tokens
        # 0..num_tokens-2): fewer means the continuation would attend
        # garbage — refuse rather than diverge
        written = max(0, len(req.prompt_tokens) + len(req.output_tokens) - 1)
        if n * cc.page_size < written:
            raise SnapshotError(
                f"{n} shipped page(s) cannot cover {written} written "
                "KV slot(s)",
                code="snapshot_corrupt",
            )
        from helix_tpu.engine.kv_cache import page_checksum

        quantized = cc.quantized
        kshape = cc.page_shapes(self.model_cfg)[0]
        entries = []
        for arrays, digest in zip(snap.pages, snap.page_checksums):
            entry = {
                f: arrays.get(f)
                for f in ("k", "v", "k_scale", "v_scale")
            }
            # a latent pool's page is its one array: "v" travels as None
            if entry["k"] is None or (
                    (entry["v"] is None) != (self.cache.v_pages is None)):
                raise SnapshotError(
                    "page missing k/v buffers", code="snapshot_corrupt"
                )
            if tuple(entry["k"].shape) != kshape:
                raise SnapshotError(
                    f"page shape {tuple(entry['k'].shape)} != pool page "
                    f"shape {kshape}",
                    code="snapshot_incompatible",
                )
            if quantized != (entry["k_scale"] is not None):
                raise SnapshotError(
                    "snapshot storage mode does not match the pool "
                    "(int8 scales present/absent)",
                    code="snapshot_incompatible",
                )
            if page_checksum(entry).hex() != digest:
                raise SnapshotError(
                    "page failed checksum verification — refusing to "
                    "restore corrupt KV",
                    code="snapshot_corrupt",
                )
            entries.append(entry)
        V = self.model_cfg.vocab_size
        counts = np.zeros((V,), np.int32)
        for tok, cnt in snap.token_counts.items():
            t = int(tok)
            if not 0 <= t < V:
                raise SnapshotError(
                    f"histogram token id {t} outside vocab {V}",
                    code="snapshot_incompatible",
                )
            counts[t] = int(cnt)
        if snap.key is None or len(snap.key) != 2:
            raise SnapshotError(
                "missing sampler key", code="snapshot_corrupt"
            )
        limit = min(n_total * cc.page_size, self.max_context_len)
        req.max_len = min(int(snap.max_len or limit), limit)
        req.cached_tokens = 0
        st = PreemptedSeq(
            req=req,
            table=np.zeros((n_total,), np.int32),  # rewritten at resume
            private_pos=list(range(n_total)),
            position=int(snap.position),
            last_token=int(snap.last_token),
            mrope_delta=int(snap.mrope_delta),
            key=np.asarray(snap.key, np.uint32),
            counts=counts,
            entries=entries,
        )
        self._requests[req.id] = req
        self.preempted.append(st)
        self.num_snapshots_imported += 1
        return req

    def _discard_preempted(self, st: PreemptedSeq) -> None:
        st.entries = None
        if self.host_pool is None:
            return   # imported-snapshot park: nothing lives in the pool
        for pos in st.private_pos:
            self.host_pool.discard(("seq", st.req.id, pos))

    def _try_resume(self) -> None:
        """Swap parked decoders back in, FIFO, while a slot + pages are
        available.  Restored pages are bit-identical to what was spilled
        (checksummed both ways), the PRNG key and penalty histogram
        rejoin the device state exactly, so a greedy or seeded
        continuation matches an unpreempted run token for token."""
        while self.preempted:
            st = self.preempted[0]
            req = st.req
            if req.finished:   # aborted while parked
                self.preempted.pop(0)
                self._discard_preempted(st)
                continue
            if self._plan_drive is not None:
                # follower: resume exactly the requests the leader
                # resumed, in plan order — local slot/page headroom may
                # transiently differ mid-plan and must not decide
                drv = self._plan_drive.resumes
                if not drv or drv[0] != req.id:
                    return
            free_slots = [
                i for i, s in enumerate(self.slots) if s is None
            ]
            n_private = len(st.private_pos)
            # _ensure_pages, not bare can_allocate: refcount-0 prefix
            # cache pages must LRU-evict (spilling to the host tier when
            # armed) for a parked resume exactly as they do for a fresh
            # admission — otherwise a pool whose free list is mostly
            # cache-owned wedges every parked/imported request
            if not free_slots or not self._ensure_pages(n_private):
                return
            resume_adapter = 0
            if getattr(req, "adapter", ""):
                # ordinary preemptions keep their adapter ref parked
                # (idempotent re-acquire); imported snapshots pin it
                # here — a cold adapter keeps the park FIFO waiting
                # while the prefetch overlaps (never blocks the step)
                got = self._acquire_adapter(req)
                if got is None:
                    self._adapter_ready(req)   # (re-)kick the prefetch
                    return
                resume_adapter = got
            # claim + verify every host copy BEFORE touching allocator
            # state: a corrupt page means the sequence cannot be
            # reconstructed bit-exactly — fail the request loudly, never
            # resume wrong KV.  One pass (checksum verified inside
            # take_restored); a mid-chain failure aborts the whole
            # resume, so a None can never reach restore_pages.
            # Imported snapshots (ISSUE 11) carry their pages INLINE,
            # verified once at import — no pool round trip.
            t0 = time.monotonic()
            if st.entries is not None:
                entries = st.entries
            else:
                entries = []
                for pos in st.private_pos:
                    e = self.host_pool.take_restored(("seq", req.id, pos))
                    if e is None:
                        break
                    entries.append(e)
            if st.entries is None and len(entries) != n_private:
                self.preempted.pop(0)
                self._discard_preempted(st)
                self._resume_failures.append(
                    (
                        req,
                        "kv_restore_corrupt: a swapped-out page failed "
                        "checksum verification on resume",
                    )
                )
                self._finish(req, FinishReason.ABORT)
                continue
            new_pages = self.allocator.allocate(req.id, n_private)
            from helix_tpu.engine.kv_cache import restore_pages

            # imported snapshots ship only the WRITTEN head of the
            # table; the tail pages just allocated stay as-is (their
            # contents are overwritten before they are ever attended)
            self.cache = restore_pages(
                self.cache, new_pages[: len(entries)], entries
            )
            st.entries = None   # inline page buffers are on device now
            table = np.array(st.table)
            for pos, pg in zip(st.private_pos, new_pages):
                table[pos] = pg
            slot = free_slots[0]
            self.slots[slot] = req
            req.slot = slot
            self._slot_adapters[slot] = resume_adapter
            row = np.zeros((self.cache_cfg.max_pages_per_seq,), np.int32)
            row[: len(table)] = table
            self._page_tables[slot] = row
            self._positions[slot] = st.position
            self._last_token[slot] = st.last_token
            self._mrope_delta[slot] = st.mrope_delta
            # the evolved key re-enters through the host mirror (the
            # changed-slot rebuild takes keys from it); the histogram
            # needs the explicit device override applied at next sync
            self._slot_keys[slot] = st.key
            self._slot_count_overrides[slot] = st.counts
            self._state_dirty = True
            self._changed_slots.add(slot)
            self.num_resumes += 1
            if self._plan_recorder is not None:
                self._plan_recorder.resumes.append(req.id)
            if self._plan_drive is not None:
                self._plan_drive.resumes.pop(0)
            self.restore_seconds += time.monotonic() - t0
            self.preempted.pop(0)
            logging.getLogger(__name__).info(
                "resumed request %s into slot %d (%d page(s) restored)",
                req.id, slot, n_private,
            )

    def drain_resume_failures(self) -> list:
        """(request, reason) pairs for resumes that failed verification —
        the engine loop turns them into typed client error events."""
        out, self._resume_failures = self._resume_failures, []
        return out

    # ------------------------------------------------------------------
    # speculative decoding (engine/spec.py + the unified ragged step)
    # ------------------------------------------------------------------

    @property
    def spec_acceptance_ratio(self) -> float:
        """Lifetime accepted/drafted ratio (0.0 before any draft)."""
        d = self.num_spec_drafted_tokens
        return self.num_spec_accepted_tokens / d if d else 0.0

    def spec_disabled_slots(self) -> int:
        """Live requests currently EMA-disabled from speculating."""
        return self.spec.disabled_count() if self.spec is not None else 0

    def _spec_width(self) -> int:
        """State-segment token width: spec_tokens + 1 (the bonus
        position) when speculation is on, 1 otherwise — EXACT on every
        backend.  The ragged kernel tiles 8-token query blocks
        internally, so pallas no longer buckets the verify width up to a
        page_size multiple (pre-unification a k=4 draft padded every
        verify call to 16 positions at page_size 16), and the history
        length is a per-row runtime value rather than a compile-shape
        bucket."""
        return 1 if self.spec is None else self.cfg.spec_tokens + 1

    def _spec_extra_steps(self) -> int:
        """Fused-window tail for a verify call: plain decode steps
        scanned onto the rolled-back state inside the same jit, so a
        spec sync never yields fewer tokens per host round trip than
        the plain window would have.  Starts from ``_decode_window()``
        (which owns the chunking/adaptive-streaming/queued-work gates)
        and shrinks while any active slot lacks headroom for the worst
        case: ``spec_tokens + 1`` verify positions plus the tail."""
        n = self._decode_window()
        if n <= 1:
            return 0
        k1 = self.cfg.spec_tokens + 1
        table_cap = (
            self.cache_cfg.max_pages_per_seq * self.cache_cfg.page_size
        )
        for i, req in enumerate(self.slots):
            if req is None or not self._slot_active(i):
                continue
            pend = self._pending_out(req)
            h = min(
                req.sampling.max_tokens - len(req.output_tokens) - pend,
                (req.max_len or self.cache_cfg.max_seq_len)
                - req.num_tokens - pend,
                table_cap - int(self._positions[i]),
            )
            while n > 1 and k1 + n - 1 > h:
                n //= 2
            if n <= 1:
                return 0
        return n - 1

    def _spec_dispatch(self) -> Optional[PendingStep]:
        """One speculative decode step: draft per slot on the host, then
        verify every slot's drafts in ONE device call.  Returns None
        when no slot drafted anything (the caller then runs the plain
        fused-window decode — speculation never makes a step slower than
        the baseline path, it only substitutes for it)."""
        k = self.cfg.spec_tokens
        ps = self.cache_cfg.page_size
        B = self.cfg.max_decode_batch
        width = self._spec_width()
        table_cap = self.cache_cfg.max_pages_per_seq * ps
        drafts = np.zeros((B, width - 1), np.int32)
        draft_len = np.zeros((B,), np.int32)
        if self._plan_drive is not None:
            # follower: drafts are DATA from the leader's plan — the
            # local drafter (whose n-gram history and EMA gating are
            # host state) never runs, so the verify call is built from
            # the exact tokens the leader verified
            for slot, toks in self._plan_drive.drafts:
                drafts[slot, : len(toks)] = toks
                draft_len[slot] = len(toks)
            if not draft_len.any():
                return None
            return self._spec_dispatch_tail(drafts, draft_len)
        for i, req in enumerate(self.slots):
            if req is None or not self._slot_active(i):
                continue
            if req.id in self._pending_first_ids:
                # deferred first token: the host-visible
                # sequence lags the device by one token, so a draft
                # would condition on the wrong suffix — sit this call
                # out (the verify would just reject it anyway)
                continue
            pos = int(self._positions[i])
            # headroom: the verify call writes KV for pos..pos+L, so the
            # draft must fit the slot's allocated pages (max_len) and is
            # not worth proposing past the remaining token budget
            pend = self._pending_out(req)
            budget = (
                req.sampling.max_tokens - len(req.output_tokens) - pend
            )
            room = (
                (req.max_len or self.cache_cfg.max_seq_len)
                - req.num_tokens - pend
            )
            cap = min(k, budget - 1, room - 1, table_cap - pos - 1)
            if cap <= 0:
                continue
            toks = self.spec.draft(
                req.id, req.prompt_tokens + req.output_tokens, cap
            )
            if not toks:
                continue
            # Stale-KV safety invariant: drafted (possibly rejected) KV
            # lands only in the slot's PRIVATE page tail past the last
            # prompt token — the prefix cache shares only full pages
            # strictly below it, so a rejected draft can never corrupt
            # KV another request reads.  Rollback is then just resetting
            # host length + DecodeState; the next step overwrites the
            # same (page, offset) slots.
            plen = len(req.prompt_tokens)
            n_shared = len(self._shared_pages.get(req.id, ()))
            assert pos >= plen and n_shared * ps <= max(plen - 1, 0), (
                f"speculative KV write would touch shared pages: slot "
                f"{i} at position {pos}, prompt {plen} tokens, "
                f"{n_shared} shared pages of {ps}"
            )
            drafts[i, : len(toks)] = toks
            draft_len[i] = len(toks)
        if not draft_len.any():
            return None
        if self._plan_recorder is not None:
            self._plan_recorder.drafts = [
                (i, [int(t) for t in drafts[i, : int(draft_len[i])]])
                for i in range(B) if draft_len[i] > 0
            ]
        return self._spec_dispatch_tail(drafts, draft_len)

    def _spec_dispatch_tail(self, drafts, draft_len) -> PendingStep:
        """The device half of a spec step: identical for a leader's
        host-drafted tokens and a follower's plan-carried ones."""
        rows = [
            (i, r) for i, r in enumerate(self.slots)
            if r is not None and self._slot_active(i)
        ]
        n_extra = self._spec_extra_steps()
        _, sampled, emit, extra = self._ragged_step(
            "spec", drafts=drafts, draft_len=draft_len, n_extra=n_extra,
        )
        self.num_spec_steps += 1
        # ONE device call for verify + the fused-window tail: with
        # accepted drafts, decode_tokens / device_steps exceeds 1 per
        # slot — that ratio IS the speculation win (tokens per forward)
        self.num_decode_device_steps += 1 + n_extra
        return PendingStep(
            kind="spec", rows=rows, handles=(sampled, emit, extra),
            n_extra=n_extra, draft_len=draft_len,
            **self._take_deferred(),
        )

    def _spec_complete(self, p: PendingStep, emitted) -> None:
        sampled_np, emit_np, extra_np = self._fetch_deferred(p, emitted)
        draft_len = p.draft_len
        for i, req in p.rows:
            if self.slots[i] is not req:
                continue
            e = int(emit_np[i])
            L = int(draft_len[i])
            if L:
                acc = min(e - 1, L)
                self.num_spec_drafted_tokens += L
                self.num_spec_accepted_tokens += acc
                self.spec.observe(req.id, L, acc)
            for j in range(e):
                if self.slots[i] is not req or req.finished:
                    break   # finished mid-verify: discard the overrun
                self._positions[i] += 1
                self._last_token[i] = sampled_np[i, j]
                self.num_decode_tokens += 1
                self._emit(req, int(sampled_np[i, j]), emitted)
        # fused-window tail tokens (same contract as the plain decode
        # window: finished slots discard the overrun)
        for s in range(p.n_extra):
            for i, req in p.rows:
                if self.slots[i] is not req or req.finished:
                    continue
                self._positions[i] += 1
                self._last_token[i] = extra_np[s, i]
                self.num_decode_tokens += 1
                self._emit(req, int(extra_np[s, i]), emitted)

    def _decode_dispatch(self) -> PendingStep:
        n = self._decode_window()
        rows = self._running_rows()
        self._check_table_room(rows, n)
        # plain decode IS the unified step with zero drafts: position 0
        # of each active row samples this step's token, and the fused
        # tail advances the remaining n-1 window steps in the same jit
        _, sampled, _, extra = self._ragged_step(
            "decode", draft_len=self._zero_rows, n_extra=n - 1,
        )
        self.num_decode_device_steps += n
        self._advance(rows, n)
        return PendingStep(
            kind="decode", rows=rows, handles=(sampled, extra), n=n,
            **self._take_deferred(),
        )

    def _decode_complete(self, p: PendingStep, emitted) -> None:
        # deferred tokens land in the SAME host round trip as the decode
        # window (ISSUE 13 satellite)
        sampled_np, extra_np = self._fetch_deferred(p, emitted)
        for _i, r in p.rows:
            self._uncharge(r, p.n)
        self._emit_row_tokens(p.rows, sampled_np[:, 0], emitted)
        for s in range(p.n - 1):
            self._emit_row_tokens(p.rows, extra_np[s], emitted)

    # ------------------------------------------------------------------
    # the unified ragged device step (ISSUE 10)
    # ------------------------------------------------------------------

    def _charge_padding(self, bucket: int, used: int) -> None:
        """THE padding formula: every prefill caller rounds its token
        axis up to a compile bucket, and the difference is forward-pass
        work spent on zeros.  One site (plus the VL single-shot path)
        so ``helix_prefill_padding_*`` can never drift between
        callers."""
        self.num_prefill_padding_tokens += max(0, int(bucket) - int(used))

    @property
    def compiled_step_shapes(self) -> int:
        """Distinct compiled device-step entry points live for this
        model (unified ragged shapes + VL prefill buckets), from the
        module-level registry — the shape-zoo collapse, observable."""
        return ragged_meta.compiled_step_shapes(self._shape_key)

    def _ragged_step(self, kind: str, plan=None, drafts=None,
                     draft_len=None, n_extra: int = 0):
        """Issue ONE unified device step: the optional prefill plan's
        ragged rows + the decode-state segment (+ a fused plain-decode
        tail of ``n_extra`` steps).  Every device-step caller routes
        here; the compiled entry point is keyed only on the prefill
        token-bucket (plus the has-history / row-capacity variants the
        plan implies).  ``kind`` names the caller on the launch's
        profiler span.  Returns ``(p_first, sampled, emit, extra)``
        device handles; a MoE step's stats vector is queued for
        ``_drain_moe_drops``."""
        if self._tiered:
            # tiered rows: demote pages behind the hot tail, then grow
            # tables to cover this step's writes — BEFORE the state sync
            # so the uploaded mirrors carry the post-demotion tables.
            # Plan rows alias the same table ndarrays (plan.add stores
            # np.asarray(table)), so mutations land in already-built
            # plans before finalize_device below reads them.
            self._tiered_prep(n_extra)
        if draft_len is None:
            draft_len = self._inert_rows
        if (
            self._state_dirty or self._dstate is None
            # a row that the tokens in flight exhaust sits this step out
            or (draft_len is not self._inert_rows and not np.array_equal(
                self._running_mask(), self._active_sent))
        ):
            self._sync_state()
        if drafts is None:
            drafts = self._zero_drafts
        pool_slots = (
            self.adapter_pool.slots if self.adapter_pool is not None
            else 0
        )
        if plan is not None and plan.rows:
            rung = bucket_tokens(plan.used, self._token_ladder)
            self._charge_padding(rung, plan.used)
            # host->device conversion happens HERE, at dispatch time:
            # under the async loop this step's metadata uploads overlap
            # the previous step's device execution (double-buffered
            # metadata — jax issues the transfers asynchronously)
            with self._part("helix.loop.plan"):
                a = plan.finalize_device(
                    rung, with_state=self.cache.state is not None)
                sampling = SamplingState.from_params(
                    [r.sampling for r in plan.rows]
                    + [SamplingParams()] * (plan.max_rows - len(plan.rows))
                )
            pargs = (
                a["tokens"], a["pos"], a["seg"], a["pages"],
                a["offsets"], a["t0"], a["qlen"], a["hist"],
                a["tables"], a["ends"], sampling, a["keys"],
            )
            if pool_slots:
                # one more per-row metadata column: each token's
                # adapter pool slot (0 = identity)
                pargs = pargs + (a["aids"],)
            if self.cache.state is not None:
                # each row's slot in the state pool, and how many of its
                # tokens lie before the boundary whose state comes back
                pargs = pargs + (a["slots"], a["snaps"])
            rows = plan.max_rows
            has_hist = plan.has_hist
        else:
            rung, rows, has_hist, pargs = 0, 0, False, ()
        ring_hist = 0
        if rows == 1 and _mesh_sp(self.mesh) > 1:
            # ring chunks gather a STATIC pow2-bucketed history capacity
            # (smallest pow2 multiple of the chunk cap covering the
            # start — the pre-unification chunk scheme), so the ring
            # payload scales with actual history, not max context
            start = max((r.start for r in plan.rows), default=0)
            if start > 0:
                hist_tokens = self.cfg.max_prefill_len
                while hist_tokens < start:
                    hist_tokens *= 2
                ring_hist = min(
                    hist_tokens // self.cache_cfg.page_size,
                    self.cache_cfg.max_pages_per_seq,
                )
        cold_arg = None
        cold_chunks = 0
        cold_ct = 0
        if self._tiered:
            staged = self._refresh_cold_staged()
            if staged is not None:
                bound = self._finalize_cold(staged, plan, rows)
                if bound is not None:
                    cold_arg, cold_chunks, cold_ct = bound
        n_tail_max = self._n_tail_max
        if self.model_cfg.loop_bodies > 2 and rung:
            # only the decode-only program runs the fused tail (every
            # caller that brings a plan passes n_extra 0); the tail holds
            # every loop body again, a third of a prefill program's compile
            # at five bodies (PERF.md section 7, 21: all models, one day)
            if n_extra:
                raise ValueError("a prefill step has no fused decode tail")
            n_tail_max = 0
        fn = _build_ragged_step_fn(
            self.model_cfg, self.cache_cfg.page_size, self._backend,
            self.mesh, rung, has_hist, rows, self._spec_width(),
            n_tail_max, ring_hist, pool_slots,
            cold_chunks, cold_ct,
        )
        self.num_device_calls += 1
        self._note_adapter_rows(plan, draft_len)
        pos = self._live_positions(draft_len)
        walked = self._note_kinds(
            plan.rows if rows else (), pos, n_extra, rung, rows)
        if self.mixer is not None:
            walked["attn_layers"] = self.model_cfg.num_attn_layers
        if self.model_cfg.num_attn_layers:
            context = int(pos.sum()) + sum(
                r.start + r.rem for r in (plan.rows if rows else ()))
            if rows:
                walked["chunk_q_block"] = self.model_cfg.page_kind.query_block(
                    self.model_cfg, rung, rows)
            walked["context_tokens"] = context
            if kind != "warmup":
                self.step_context_tokens = context
                self.chunk_q_block = walked.get(
                    "chunk_q_block", self.chunk_q_block)
        used = plan.used if rows else 0
        live_rows = int(np.count_nonzero(np.asarray(draft_len) >= 0))
        joint_pass = int(rows > 0)
        inert_rows = (len(draft_len) - live_rows) * joint_pass
        self.num_joint_pass_steps += joint_pass
        self.num_joint_pass_inert_rows += inert_rows
        with self._part(
            "helix.loop.launch", kind=kind, token_bucket=rung,
            prefill_rows=rows, has_hist=has_hist,
            live_rows=live_rows, joint_pass=joint_pass,
            inert_rows=inert_rows,
            n_extra=int(n_extra), prefill_tokens=used,
            padding_tokens=rung - used,
            **({"experts_touched": round(self.moe_experts_touched, 1)}
               if self.model_cfg.num_experts else {}),
            **({"grouped_backend": self.grouped_backend}
               if self.grouped_backend else {}),
            attn_q_block=self.attn_q_block,
            **({"held_experts": self.model_cfg.num_held_experts}
               if self.model_cfg.held_experts else {}),
            **walked,
        ):
            if self.first_launch_time is None:
                self.first_launch_time = time.monotonic()
            (self.cache, self._dstate, p_first, sampled, emit, extra,
             drops, *snaps) = fn(
                self._graft_params(), self.cache, self._dstate, pargs,
                jnp.asarray(drafts), jnp.asarray(draft_len),
                jnp.int32(n_extra), cold_arg,
            )
        if snaps and snaps[0] is not None:
            self._note_boundary_states(plan, snaps[0])
        if drops is not None:
            # fetched when ready, after this step's own fetch
            self._moe_drop_handles.append(drops)
        return p_first, sampled, emit, extra

    def _note_boundary_states(self, plan, snaps) -> None:
        """Note, for each prefill row that passed a page boundary, where the
        conv state the step handed back for it lies (``snaps [conv layers,
        rows, K - 1, E]``, left on the device: nothing is fetched) until
        the prompt's pages are adopted and it is filed under their digest."""
        with obs_trace.phase("helix.state.snapshot", into=self.step_phases):
            ps = self.cache_cfg.page_size
            for j, row in enumerate(plan.rows):
                if row.req is None or row.snap <= 0:
                    continue
                # (the row's slice is cut when it is filed, once a prompt:
                # a device operation a row here is 2 ms a step of the host)
                self._boundary_states.setdefault(row.req.id, {})[
                    (row.start + row.snap) // ps] = (snaps, j)
                self.num_state_snapshots += 1

    # ------------------------------------------------------------------
    # completion
    # ------------------------------------------------------------------

    def _emit(self, req: Request, token: int, emitted: list) -> None:
        req.output_tokens.append(token)
        self.num_generated_tokens += 1
        emitted.append((req, token))
        stop_ids = set(req.stop_token_ids) | set(self.cfg.eos_token_ids)
        if token in stop_ids:
            self._finish(req, FinishReason.STOP)
        elif len(req.output_tokens) >= req.sampling.max_tokens:
            self._finish(req, FinishReason.LENGTH)
        elif req.num_tokens >= (req.max_len or self.cache_cfg.max_seq_len):
            self._finish(req, FinishReason.LENGTH)

    def _adopt_prompt_pages(self, req: Request, table) -> None:
        """After a prompt is fully resident, hand its fresh full pages to
        the prefix cache so the next request with the same prefix skips
        them.  Pages acquired FROM the cache are already shared; only the
        newly prefilled full pages transfer ownership (detached from the
        allocator so request teardown can't free them out from under a
        future sharer)."""
        if req.slot is not None and req.slot in self._tiered:
            # tiered tables grow lazily and demote — prompt pages may
            # already be host-resident, so neither prefix adoption nor
            # filestore write-through can gather them from the device
            return
        if self.prefix_cache is None:
            return
        hashes = self._prompt_hashes(req)
        if not hashes:
            return
        ps = self.cache_cfg.page_size
        k_shared = req.cached_tokens // ps
        fresh_hashes = hashes[k_shared:]
        if not fresh_hashes:
            return
        fresh_pages = [
            int(table[i]) for i in range(k_shared, len(hashes))
        ]
        adopted = self.prefix_cache.adopt(fresh_hashes, fresh_pages)
        for pages_in, (snaps, row) in self._boundary_states.pop(
                req.id, {}).items():
            # the conv state at a boundary the prompt's steps passed: what
            # lets a later request share the pages up to it
            if pages_in <= len(hashes) and self.prefix_cache.state_at(
                    hashes[pages_in - 1]) is None:
                self.prefix_cache.file_state(
                    hashes[pages_in - 1], snaps[:, row])
        if adopted:
            self.allocator.detach(req.id, adopted)
            # the request keeps USING them (refcount 1 held on its
            # behalf); release on finish
            self._shared_pages.setdefault(req.id, []).extend(adopted)
        if self.kv_filestore is not None:
            # write-through to the persistent rung (ISSUE 14): freshly
            # prefilled full pages persist so a restarted process (or a
            # brand-new decode-pool runner on the shared filesystem)
            # serves this prefix without recomputing it.  Quota'd per
            # tenant; a rejected write is a counter, never an error.
            self._store_filestore_pages(req, fresh_hashes, fresh_pages)

    def _store_filestore_pages(
        self, req: Request, hashes: list, pages: list
    ) -> None:
        """Persist freshly prefilled full prefix pages to the filestore
        tier.  One device gather for the not-yet-stored subset; runs at
        adoption time (the prefill device call has completed, so the
        gathered buffers hold the written KV).  The gather returns NEW
        device buffers (safe against page reuse), and the engine thread
        only dispatches it — the D2H fetch, encode, and disk write run
        on the store's background writer (``put_async``), so the tier
        never stalls the step loop."""
        from helix_tpu.engine.kv_cache import gather_pages

        want = [
            (h, p) for h, p in zip(hashes, pages)
            if not self.kv_filestore.contains(h)
        ]
        if not want:
            return
        try:
            arrays = gather_pages(self.cache, [p for _h, p in want])
            tenant = getattr(req, "tenant", "")
            for (h, _p), page_arrays in zip(want, arrays):
                self.kv_filestore.put_async(h, page_arrays, tenant=tenant)
        except Exception:  # noqa: BLE001 — the tier degrades, never fails serving
            logging.getLogger(__name__).exception(
                "KV filestore write-through failed for request %s",
                req.id,
            )

    def _finish(self, req: Request, reason: FinishReason) -> None:
        req.finished = True
        req.finish_reason = reason
        if req.slot is not None:
            led = self._tiered.pop(req.slot, None)
            if led is not None:
                # drop the cold pages' host residency and any staged
                # chunk slab that references them
                for idx in range(led["lo"], led["hi"]):
                    self.host_pool.discard(("ctx", led["rid"], idx))
                self._cold_staged = None
            self.slots[req.slot] = None
            self._state_dirty = True
            self._changed_slots.add(req.slot)
            req.slot = None
        if req in self.waiting:   # aborted before admission
            self.waiting.remove(req)
        for st in list(self.preempted):   # aborted while parked
            if st.req is req:
                self.preempted.remove(st)
                self._discard_preempted(st)
        shared = self._shared_pages.pop(req.id, None)
        if shared and self.prefix_cache is not None:
            self.prefix_cache.release(shared)
        self._boundary_states.pop(req.id, None)
        if self.spec is not None:
            self.spec.forget(req.id)
        self._release_adapter(req)
        if self.allocator.owns(req.id):
            self.allocator.free(req.id)
