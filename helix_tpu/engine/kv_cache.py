"""Paged KV cache: device-side page pool + host-side allocator.

The TPU replacement for vLLM's PagedAttention block manager (which the
reference rides inside its CUDA containers — ``SURVEY.md`` §2.2).  Design:

- Device state is two arrays per model, ``k_pages``/``v_pages`` of shape
  ``[num_layers, num_pages, page_size, kv_heads, head_dim]`` — statically
  shaped so every jitted step reuses one executable.  The layer dim leads so
  the model's ``lax.scan`` slices per-layer views.  ``[kv_heads, head_dim]``
  are minormost so ONE token's K (the KV-write scatter's update block) is
  contiguous in the default row-major layout — with heads ahead of pages the
  scatter preferred a transposed layout and XLA relaid the whole multi-GiB
  pool out and back *inside the decode loop* (the r3 profiler trace showed
  ~40% of each decode window in those copies).  A ``(layer, page)`` slice is
  a contiguous ``[page_size, kv_heads, head_dim]`` block — the DMA unit the
  Pallas decode kernel streams HBM->VMEM (one DMA per page for ALL heads).
- The page pool shards over the mesh on the kv-head axis (follows tensor
  parallelism; pages axis stays unsharded so any page can host any sequence).
- Allocation/free is pure host Python (a free list) — it never appears in a
  traced function; the device only ever sees page-table *arrays*.
- Writes take the model's stacked fresh KV ``[L, B, S, KVH, D]`` and one
  scatter places all layers/tokens; slot -> (page, offset) math happens on
  host or in cheap integer ops.

HBM cost per page = ``2 * L * page_size * KVH * D * itemsize`` — the unit the
residency manager (``engine/residency.py``) budgets with, replacing the
reference's GPU VRAM accounting.  ``L`` counts the layers that HAVE pages
(``ModelConfig.num_attn_layers``); a head width that divides the 128 lanes
is stored with ``128 / D`` kv heads side by side in one lane tile (``[KVH /
pack, pack * D]``: the same bytes, and whole tiles for the kernel's DMAs).

A second kind of state lives beside the pages: a layer whose memory is not
a token's K/V keeps, for each sequence, arrays of one size whatever the
sequence's length.  That is the STATE POOL, ``PagedKVCache.state``, one row a
decode slot: created with the cache, donated and returned by the step with
it, counted in ``CacheConfig.total_bytes`` and ``fit_hbm``.  WHICH arrays a
layer and slot, in which dtype, is the kind's record's
(``models/mixers.py::STATE_MIXERS``: ``arrays``, ``pool_dtype``, through
``ModelConfig.state_arrays``); this module gives them the axes ``[layers of
the kind, slots, ...]`` and holds one array as it is, several as a tuple
(updated in place, never copied: a matrix state is tens of MB a layer and
slot).  Where the kind's steps hand back boundary states (``snapshots``) a
prefix is pages AND a state: ``PrefixCache`` files the state a step returns
for a page boundary under that boundary's chain digest and matches only up
to a boundary that has one.

The page pool's layer axis counts the layers WITH pages alone (latent ones
where the model's attention is latent): admission, ``max_pages_per_seq`` and
``kv_pages_used`` count their pages.  A model with NO such layer has a page
pool of no bytes: pages are then only the bookkeeping of tokens a sequence
(admission against ``num_pages``, ``max_pages_per_seq``, ``kv_pages_used``),
and ``fit_hbm`` sizes SLOTS against the budget, not pages.  A slot's state is
not cleared when the slot is claimed: a row that starts its sequence reads
none of it.

WHICH arrays the page pool has, what each holds and one page's shape is the
record's of the kind of the model's pages (``models/mixers.py::PAGE_KINDS``:
``pools``, through ``CacheConfig.pools``); this module gives them the axes
``[layers with pages, pages, *page]`` and holds the first as ``k_pages``, the
second, where the kind has one, as ``v_pages``.  Three kinds stand there:

- ``kv``: K and V ``[L, N, P, KVH, D]``, with int8 storage their scale pools
  beside them.
- ``latent`` (MLA): ONE array, ``k_pages [L, N, P, R + 128]``: a token caches
  one row a layer, its normed latent in lanes ``0..R`` and its rope key
  behind it, zero-padded to whole 128-lane tiles (``mixers.latent_widths``).
  The values are the latent lanes of the same row, so attention has no second
  array (``v_pages`` is ``None``) and a ``(layer, page)`` slice is one
  contiguous block that the latent kernel fetches with ONE DMA (two arrays
  cost it two starts and two waits a page, and issuing them paced it: PERF.md
  section 6, PR 40).  Every path that moves pages as opaque buffers (host
  pool, snapshots, checksums, filestore, prefix cache) carries ``"v": None``
  for such a page.
- ``latent_indexed`` (behind a sparse-attention INDEXER) a token caches a
  second row a layer: its index key.  That is the INDEX-KEY POOL, ``v_pages
  [L, N, P, Di]`` in the pool's dtype, addressed by the SAME page ids and
  page tables as the latent pool beside it (a page is a page of both:
  admission, ``kv_pages_used`` and the prefix cache, which shares page ids
  and never looks inside one, count and share them as one); ``write_kv``
  scatters both from the one fresh pair ``(c, [k_pe | k_idx])``;
  ``page_bytes`` / ``total_bytes`` / ``fit_hbm`` count both.  The paths that
  move a page's CONTENTS off the device are refused for such a model by name
  (the record's ``refusals`` and ``call_refusal``: the host tier, tiered
  residency, request export / import, the filestore).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from helix_tpu.models.common import ModelConfig


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    num_pages: int
    page_size: int = 16
    max_pages_per_seq: int = 128
    # Page-pool storage dtype.  "int8" stores K/V codes at 1 byte/elem
    # plus per-(slot, kv-head) f32 scale pools whose page rows are
    # ``KVH*P`` wide, padded to the TPU's 128 lanes in HBM — page bytes
    # drop to (D + 4) / (2 * D) of bf16 when ``KVH*P`` fills whole lane
    # rows (Llama-3-8B at page 16: ~1.94x the pages) and to (D + 8) /
    # (2 * D) when it fills half of one (Qwen2-7B: ~1.88x).
    dtype: str = "bfloat16"
    # rows of the state pool (the engine's decode slots); a model with no
    # recurrent layers has no such pool whatever this says
    state_slots: int = 0

    @property
    def quantized(self) -> bool:
        return self.dtype == "int8"

    def state_shapes(self, model: ModelConfig) -> tuple:
        """``((shape, dtype), ...)`` of the state pool's arrays, by the kind
        of the model's layers with a per-sequence state; empty for a model
        without one.  A kind whose arrays are K and V like the pages beside
        them (``StateMixer.pool_dtype``) has them in the POOL's dtype."""
        kind = model.state_kind
        return tuple(
            ((model.num_state_layers, self.state_slots) + tuple(shp),
             self.dtype if kind.pool_dtype else dt)
            for shp, dt in model.state_arrays())

    def state_shape(self, model: ModelConfig) -> Optional[tuple]:
        """The state pool's (first array's) shape, ``None`` for a model
        without one."""
        shapes = self.state_shapes(model)
        return shapes[0][0] if shapes else None

    def state_bytes(self, model: ModelConfig) -> int:
        return sum(int(np.prod(shp)) * jnp.dtype(dt).itemsize
                   for shp, dt in self.state_shapes(model))

    @property
    def max_seq_len(self) -> int:
        return self.page_size * self.max_pages_per_seq

    def pools(self, model: ModelConfig) -> tuple:
        """The arrays of the page pool, in the order ``PagedKVCache`` holds
        them, by the kind of the model's pages (its record's ``pools``:
        ``models/mixers.py::PAGE_KINDS``)."""
        return model.page_kind.pools(model, self.page_size)

    def page_shapes(self, model: ModelConfig) -> tuple:
        """Shapes of ONE page, all layers, in each of the pool's arrays
        (what ``gather_pages`` hands out and a snapshot carries): K and V
        ``[L, P, KVH, D]``, or for latent attention the ONE array's ``[L,
        P, R + 128]``, behind an indexer the index-key pool's ``[L, P, Di]``
        too."""
        L = model.num_attn_layers
        return tuple((L,) + p.page for p in self.pools(model))

    def geometry(self, model: ModelConfig) -> tuple:
        """``(kv_heads, head_dim)`` as a snapshot and the filestore's
        namespace state a pool (the record's ``geometry``)."""
        return model.page_kind.geometry(model)

    def pool_bytes(self, model: ModelConfig) -> dict:
        """``{what an array of the page pool holds: its bytes}``, the scale
        pools of an int8 pool left out."""
        item = jnp.dtype(self.dtype).itemsize
        return {p.holds: self.num_pages * int(np.prod(shp)) * item
                for p, shp in zip(self.pools(model), self.page_shapes(model))}

    def page_bytes(self, model: ModelConfig) -> int:
        # what is allocated, lane padding included
        total = sum(
            int(np.prod(shp)) for shp in self.page_shapes(model)
        ) * jnp.dtype(self.dtype).itemsize
        if self.quantized:
            # f32 scale per (token slot, kv head) of each array that has
            # scales, in page rows padded to whole 128-lane rows
            row = -(-self.page_size * model.num_kv_heads // 128) * 128
            total += sum(p.scaled for p in self.pools(model)) * (
                model.num_attn_layers * row * 4)
        return total

    def total_bytes(self, model: ModelConfig) -> int:
        return self.num_pages * self.page_bytes(model) + self.state_bytes(
            model)

    @classmethod
    def fit_hbm(
        cls,
        model: ModelConfig,
        hbm_budget_bytes: int,
        page_size: int = 16,
        max_pages_per_seq: int = 128,
        dtype: str = "bfloat16",
        state_slots: int = 0,
    ) -> "CacheConfig":
        """Size the page pool to an HBM budget (what's left after weights) —
        the accounting the reference does per-GPU with
        ``--gpu-memory-utilization`` on vLLM, done natively here.
        ``dtype="int8"`` budgets codes + lane-padded scale pools (see
        ``page_bytes``)."""
        probe = cls(num_pages=1, page_size=page_size,
                    max_pages_per_seq=max_pages_per_seq, dtype=dtype,
                    state_slots=state_slots)
        per_page = probe.page_bytes(model)
        if per_page == 0:
            # no layer has pages: the budget buys SLOTS of state, and the
            # page table is bookkeeping (room for every slot's longest
            # sequence, and the garbage page)
            per_slot = probe.state_bytes(model) // max(state_slots, 1)
            slots = min(state_slots, hbm_budget_bytes // max(per_slot, 1))
            return dataclasses.replace(
                probe, state_slots=int(slots),
                num_pages=int(slots) * max_pages_per_seq + 1)
        # the state pool comes out of the budget first: its size follows
        # the slots, not the pages
        left = hbm_budget_bytes - probe.state_bytes(model)
        num_pages = max(left // per_page, 0)
        return dataclasses.replace(probe, num_pages=int(num_pages))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """Device page pool (a pytree — passes through jit with donation).

    With an int8 pool the per-(slot, head) f32 scale pools ``k_scale`` /
    ``v_scale`` ride along as lane-dense page rows ``[L, N, KVH*P]``
    (``ops.quant.pack_scale_pages``); they are ``None``
    for full-precision pools so the pytree structure itself encodes the
    storage mode (jit re-traces on the structural change, no static flag
    needed).
    """

    # the first and the second array of the model's page kind
    # (``CacheConfig.pools``: the record says what each holds), ``[L, N,
    # *page]``; None where the kind has no second
    k_pages: jax.Array
    v_pages: Optional[jax.Array]
    k_scale: Optional[jax.Array] = None  # [L, N, KVH*P] f32 (int8 pools)
    v_scale: Optional[jax.Array] = None
    # the state pool: the arrays of the model's state kind
    # (``CacheConfig.state_shapes``), one as it is, several as a tuple; None
    # for a model whose memory is pages alone
    state: Optional[object] = None

    @classmethod
    def create(
        cls,
        model: ModelConfig,
        cache: CacheConfig,
        mesh=None,
    ) -> "PagedKVCache":
        """Pages for the layers that have them (by the kind of the model's
        pages; no bytes where there are none) and a state pool for the ones
        with a per-sequence state."""
        pools = cache.pools(model)
        shapes = [(shp[0], cache.num_pages) + shp[1:]
                  for shp in cache.page_shapes(model)]
        if model.state_mixer:
            arrays = tuple(jnp.zeros(shp, jnp.dtype(dt))
                           for shp, dt in cache.state_shapes(model))
            return cls._on_one_device(
                shapes, cache, mesh, "a page pool beside a state pool",
                state=arrays[0] if len(arrays) == 1 else arrays)
        if not all(p.scaled for p in pools):
            return cls._on_one_device(
                shapes, cache, mesh,
                f"the page pool of {model.page_kind.refused_as}")
        if model.kv_head_pack > 1 and cache.quantized:
            raise ValueError(
                f"a page pool of head width {model.head_dim} packs "
                f"{model.kv_head_pack} kv heads into a lane tile and "
                "has no int8 storage (its scales are one a head): set "
                "kv_cache_dtype to auto, bfloat16 or float32"
            )
        shape, dtype = shapes[0], jnp.dtype(cache.dtype)
        sshape = (
            model.num_layers,
            cache.num_pages,
            model.num_kv_heads * cache.page_size,
        )
        if mesh is not None:
            from helix_tpu.parallel.sharding import logical_sharding

            # leading L follows the pp layer sharding: each pipeline
            # group holds ONLY its own layers' KV pages (KV dominates
            # serving HBM; replicating it would forfeit most of pp's
            # capacity win). Meshes without pp prune it to replicated.
            sharding = logical_sharding(
                mesh, ("layers", "pages", None, "cache_heads", None)
            )
            zeros = jax.jit(
                lambda: jnp.zeros(shape, dtype), out_shardings=(sharding)
            )
            k = zeros()
            v = zeros()
            if cache.quantized:
                ssharding = logical_sharding(
                    mesh, ("layers", "pages", "cache_heads")
                )
                szeros = jax.jit(
                    lambda: jnp.zeros(sshape, jnp.float32),
                    out_shardings=(ssharding),
                )
                return cls(
                    k_pages=k, v_pages=v, k_scale=szeros(),
                    v_scale=szeros(),
                )
        else:
            k = jnp.zeros(shape, dtype)
            v = jnp.zeros(shape, dtype)
            if cache.quantized:
                return cls(
                    k_pages=k,
                    v_pages=v,
                    k_scale=jnp.zeros(sshape, jnp.float32),
                    v_scale=jnp.zeros(sshape, jnp.float32),
                )
        return cls(k_pages=k, v_pages=v)

    @classmethod
    def _on_one_device(cls, shapes, cache, mesh, what,
                       state=None) -> "PagedKVCache":
        """The pool's arrays (two, or one: where a K/V pool has its second,
        ``None``, and every opaque page path carries it as such: host pool,
        snapshots, checksums, filestore), bf16 or f32, on one device: what
        the pools without an int8 or a sharded form are."""
        if cache.quantized:
            raise ValueError(
                f"{what} has no int8 storage: set kv_cache_dtype to auto, "
                "bfloat16 or float32"
            )
        if mesh is not None and mesh.devices.size > 1:
            raise ValueError(
                f"{what} is held by one device: a mesh of "
                f"{mesh.devices.size} devices is not supported"
            )
        pools = [jnp.zeros(shp, jnp.dtype(cache.dtype)) for shp in shapes]
        return cls(k_pages=pools[0],
                   v_pages=pools[1] if len(pools) > 1 else None, state=state)

    @property
    def latent(self) -> bool:
        """No head axis: a latent (MLA) pool."""
        return self.k_pages.ndim == 4

    @property
    def num_layers(self):
        return self.k_pages.shape[0]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def carry(self):
        """The pytree threaded through decode scans / prefill xs: pools
        plus scale pools when quantized (leaves all carry a leading L; a
        latent pool's second entry is ``None``, no leaf)."""
        if self.k_scale is None:
            return (self.k_pages, self.v_pages)
        return (self.k_pages, self.v_pages, self.k_scale, self.v_scale)

    @classmethod
    def from_carry(cls, carry, state=None) -> "PagedKVCache":
        """The page pools back from a scan's carry, beside ``state`` (the
        state pool is threaded on its own: it has another layer axis)."""
        if len(carry) == 2:
            return cls(k_pages=carry[0], v_pages=carry[1], state=state)
        return cls(
            k_pages=carry[0], v_pages=carry[1],
            k_scale=carry[2], v_scale=carry[3], state=state,
        )


def write_kv(
    cache: PagedKVCache,
    k_new: jax.Array,  # [L, B, S, KVH, D]
    v_new: jax.Array,
    pages: jax.Array,   # [B, S] int32 — destination page per token
    offsets: jax.Array, # [B, S] int32 — offset within page
    valid: jax.Array,   # [B, S] bool — False for padding tokens
) -> PagedKVCache:
    """Scatter fresh KV into the pool in one op.

    Padding tokens are routed to a reserved scratch page (page 0 is kept as
    the engine's garbage page) so the scatter stays fully dense.

    Int8 pools quantize here (per-slot-per-head absmax scales) and scatter
    the f32 scale rows into the scale pools with the same fused index.
    """
    if cache.latent:
        return _write_latent(cache, k_new, v_new, pages, offsets, valid)
    L, B, S, KVH, D = k_new.shape
    Lp, P, ps, KVHp, Dp = cache.k_pages.shape
    # Scatter at ONE fused token index (page*page_size + offset) into a
    # [L, P*ps, KVH, D] view of the pool.  One update block = a token's
    # [KVH, D] — contiguous under the pool's default row-major layout, so
    # XLA keeps that layout (a (page, offset) two-index scatter, or a pool
    # with heads ahead of pages, makes layout assignment flip the pool and
    # copy multi-GiB temporaries).  The reshapes are bitcasts (pages and
    # offset are adjacent, contiguous dims).
    flat_idx = jnp.where(
        valid, pages * ps + offsets, 0
    ).reshape(-1)
    k_sc = v_sc = None
    if cache.quantized:
        from helix_tpu.ops.quant import quantize_kv

        k_new, k_sc = quantize_kv(k_new)   # int8 + [L, B, S, KVH] f32
        v_new, v_sc = quantize_kv(v_new)
    # (a packed pool's minor pair is [KVH / pack, pack * D]: the same
    # values in the same order)
    kf = k_new.reshape(L, B * S, KVHp, Dp).astype(cache.k_pages.dtype)
    vf = v_new.reshape(L, B * S, KVHp, Dp).astype(cache.v_pages.dtype)
    k_pages = (
        cache.k_pages.reshape(Lp, P * ps, KVHp, Dp)
        .at[:, flat_idx]
        .set(kf, mode="drop", unique_indices=False)
        .reshape(Lp, P, ps, KVHp, Dp)
    )
    v_pages = (
        cache.v_pages.reshape(Lp, P * ps, KVHp, Dp)
        .at[:, flat_idx]
        .set(vf, mode="drop", unique_indices=False)
        .reshape(Lp, P, ps, KVHp, Dp)
    )
    if not cache.quantized:
        return PagedKVCache(k_pages=k_pages, v_pages=v_pages,
                            state=cache.state)
    # scale pools are [L, N, KVH*ps] page rows, head-major in a page: a
    # token's KVH scales sit ps lanes apart, so the scatter indexes
    # (page, offset) on a [L, N, KVH, ps] view with the head axis a
    # window — which also keeps it local when heads are sharded
    pg = jnp.where(valid, pages, 0).reshape(-1)
    off = jnp.where(valid, offsets, 0).reshape(-1)

    def scatter_scales(pool, sc):
        return (
            pool.reshape(Lp, P, KVHp, ps)
            .at[:, pg, :, off]
            .set(sc.reshape(L, B * S, KVH).swapaxes(0, 1), mode="drop",
                 unique_indices=False)
            .reshape(Lp, P, KVHp * ps)
        )

    return PagedKVCache(
        k_pages=k_pages, v_pages=v_pages,
        k_scale=scatter_scales(cache.k_scale, k_sc),
        v_scale=scatter_scales(cache.v_scale, v_sc),
    )


def _write_latent(cache, c_new, r_new, pages, offsets, valid):
    """``write_kv`` for a latent pool: ``c_new [L, B, S, R]`` and ``r_new
    [L, B, S, dr]`` joined into the pool's rows ``[c | r | zeros]``
    (beside an index-key pool ``r_new`` is ``[r | k_idx]`` and its last
    ``Di`` lanes are scattered into that pool at the same rows).  ONE
    row scatter over the pool viewed ``[L * N * ps, R + 128]``, each
    (layer, token) its own row index: a pool with no head axis has only
    the lane axis minor, and a scatter that kept the layer axis as a
    window made layout assignment move it next to the lanes and copy the
    whole pool (3.75 GB of temporaries on a 16 GB chip, compiled for the
    described chip)."""
    pool = cache.k_pages
    L, N, ps, W = pool.shape
    tok = jnp.where(valid, pages * ps + offsets, 0).reshape(-1)     # [T]
    rows = (jnp.arange(L, dtype=tok.dtype)[:, None] * (N * ps)
            + tok[None, :]).reshape(-1)                              # [L*T]
    idx_pool = cache.v_pages
    if idx_pool is not None:
        Di = idx_pool.shape[-1]
        r_new, i_new = r_new[..., :-Di], r_new[..., -Di:]
        idx_pool = (
            idx_pool.reshape(L * N * ps, Di)
            .at[rows]
            .set(i_new.reshape(-1, Di).astype(idx_pool.dtype), mode="drop",
                 unique_indices=False)
            .reshape(L, N, ps, Di)
        )
    new = jnp.concatenate(
        [c_new.reshape(-1, c_new.shape[-1]),
         r_new.reshape(-1, r_new.shape[-1])], axis=-1).astype(pool.dtype)
    new = jnp.pad(new, ((0, 0), (0, W - new.shape[-1])))
    k_pages = (
        pool.reshape(L * N * ps, W)
        .at[rows]
        .set(new, mode="drop", unique_indices=False)
        .reshape(L, N, ps, W)
    )
    return PagedKVCache(k_pages=k_pages, v_pages=idx_pool,
                        state=cache.state)


class PageAllocator:
    """Host-side free-list allocator for the page pool.

    Page 0 is reserved as the garbage page that padding writes land on
    (``write_kv``), so it is never handed out.

    Invariants, enforced loudly (ISSUE 6): ``used + free == num_pages - 1``
    after every operation, ``free()`` of a sequence that owns nothing is
    an error (double-free / typo'd seq id), ``give_back()`` of a page
    already on the free list is an error, and ``allocate()`` either
    fully succeeds or changes nothing — a partial failure can never
    orphan pages.
    """

    def __init__(self, num_pages: int, max_pages_per_seq: int):
        self.num_pages = num_pages
        self.max_pages_per_seq = max_pages_per_seq
        self._free = list(range(num_pages - 1, 0, -1))  # page 0 reserved
        self._free_set = set(self._free)   # O(1) double-give_back guard
        self._owned: dict[str, list[int]] = {}
        self.peak_used = 0   # high-water mark of occupied pages (metrics)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        """Occupied pages (incl. prefix-cache-owned); garbage page 0 is
        outside both used and free."""
        return self.num_pages - 1 - len(self._free)

    def pages_needed(self, num_tokens: int, page_size: int) -> int:
        return -(-num_tokens // page_size)

    def can_allocate(self, n: int) -> bool:
        return len(self._free) >= n

    def allocate(self, seq_id: str, n: int) -> list[int]:
        """All-or-nothing: every failure path is checked BEFORE any page
        leaves the free list, so a raising allocate leaves no orphans."""
        if n < 0:
            raise ValueError(f"allocate({seq_id!r}, {n}): negative count")
        if n == 0:
            return []
        if len(self._free) < n:
            raise MemoryError(
                f"page pool exhausted: want {n}, have {len(self._free)}"
            )
        if len(self._owned.get(seq_id, ())) + n > self.max_pages_per_seq:
            raise MemoryError(f"sequence {seq_id} exceeds max_pages_per_seq")
        got = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(got)
        if self.used_pages > self.peak_used:
            self.peak_used = self.used_pages
        self._owned.setdefault(seq_id, []).extend(got)
        return got

    def seq_pages(self, seq_id: str) -> list[int]:
        return list(self._owned.get(seq_id, []))

    def owns(self, seq_id: str) -> bool:
        """Does this sequence currently own any pages?  Callers with a
        legitimately-maybe-unallocated sequence (a request aborted while
        still queued) guard ``free()`` with this instead of relying on a
        silent no-op that would also mask real double-frees."""
        return seq_id in self._owned

    def free(self, seq_id: str) -> None:
        if seq_id not in self._owned:
            raise KeyError(
                f"free() of sequence {seq_id!r} that owns no pages "
                "(double free, or never allocated?)"
            )
        pages = self._owned.pop(seq_id)
        self._free.extend(reversed(pages))
        self._free_set.update(pages)

    def detach(self, seq_id: str, pages: list) -> None:
        """Remove ``pages`` from the sequence's ownership WITHOUT freeing
        them — the prefix cache adopts them; they re-enter the free list
        only through give_back() on eviction."""
        drop = set(pages)
        owned = self._owned.get(seq_id)
        if owned:
            self._owned[seq_id] = [p for p in owned if p not in drop]

    def give_back(self, pages: list) -> None:
        """Return cache-evicted pages to the free list."""
        dup = self._free_set.intersection(pages)
        if dup:
            raise ValueError(
                f"give_back() of already-free page(s) {sorted(dup)}"
            )
        self._free.extend(pages)
        self._free_set.update(pages)


def slot_to_page_offset(slots: jax.Array, page_table, page_size: int):
    """(page, offset) for absolute slot indices given per-seq page tables.

    ``slots``: [B, S] absolute token positions; ``page_table``: [B, maxP].
    Decode callers pass ``positions[:, None]`` for S=1.
    """
    page_idx = slots // page_size
    offsets = slots % page_size
    pages = jnp.take_along_axis(page_table, page_idx, axis=-1)
    return pages.astype(jnp.int32), offsets.astype(jnp.int32)


class PrefixCache:
    """Automatic prefix caching: content-hashed full pages of prompt KV
    shared across requests (vLLM's APC — the reference serves through
    vLLM where this is the flagship TTFT feature for shared system
    prompts; SURVEY.md §2.2).

    Pages enter the cache when a request's prompt finishes prefilling
    (``adopt``) and are then OWNED by the cache: the allocator's ``free``
    no longer returns them (they are detached from the request), and they
    go back to the free list only via LRU eviction under allocation
    pressure.  A later request whose prompt starts with the same page
    contents ``acquire``s them (refcount++) and skips prefilling those
    tokens entirely — attention reads them as history through the page
    table, which is safe because decode only ever writes pages PAST the
    shared prefix.

    Hash chain: h_i = blake2b(h_{i-1} || tokens[i*ps:(i+1)*ps]) — a page
    matches only when its entire prefix matches, so a page table can be
    stitched from the longest cached run.
    """

    def __init__(self, stateful: bool = False):
        self._entries: dict[bytes, list] = {}   # digest -> [page, refs, tick]
        self._by_page: dict[int, bytes] = {}
        # a model with recurrent state: a prefix is pages AND the state at
        # its end.  digest -> the state at that page boundary (whatever the
        # engine files: a device array); evicted with the boundary's page
        self.stateful = stateful
        self._states: dict[bytes, object] = {}
        self._tick = 0
        self.hits = 0          # pages served from cache
        self.misses = 0        # full pages prefilled fresh
        self.evicted_pages = 0  # pages LRU-evicted under allocation pressure

    @staticmethod
    def page_hashes(tokens, page_size: int, max_pages: int) -> list:
        """Chain digests for the first ``max_pages`` FULL pages."""
        import hashlib

        out = []
        prev = b""
        for i in range(max_pages):
            chunk = tokens[i * page_size:(i + 1) * page_size]
            if len(chunk) < page_size:
                break
            h = hashlib.blake2b(digest_size=16)
            h.update(prev)
            h.update(np.asarray(chunk, np.int32).tobytes())
            prev = h.digest()
            out.append(prev)
        return out

    def match_len(self, hashes: list, pages_only: bool = False) -> int:
        """Longest cached prefix (pages), without acquiring.  A stateful
        cache walks back to the longest boundary whose state is on file
        (0 if none is): a sequence is never resumed from pages alone.
        ``pages_only`` gives the match before that walk."""
        n = 0
        for h in hashes:
            if h not in self._entries:
                break
            n += 1
        if self.stateful and not pages_only:
            while n and hashes[n - 1] not in self._states:
                n -= 1
        return n

    def file_state(self, digest: bytes, state) -> None:
        """File the recurrent state at the end of the page ``digest``
        names (first filing wins: the state is a function of the chain)."""
        if digest in self._entries:
            self._states.setdefault(digest, state)

    def state_at(self, digest: bytes):
        return self._states.get(digest)

    def acquire(self, hashes: list) -> list:
        """Claim the longest cached prefix; returns its pages (refs++).
        Does NOT touch the hit/miss counters — a claim can still fail on
        page pressure and be released; the engine records hits only for
        admissions that actually start (record_claim)."""
        pages = []
        self._tick += 1
        for h in hashes:
            e = self._entries.get(h)
            if e is None:
                break
            e[1] += 1
            e[2] = self._tick
            pages.append(e[0])
        return pages

    def record_claim(self, hit_pages: int, total_pages: int) -> None:
        """Stats for ONE admitted request: pages served from cache vs
        full pages prefilled fresh."""
        self.hits += hit_pages
        self.misses += total_pages - hit_pages

    def release(self, pages: list) -> None:
        for p in pages:
            h = self._by_page.get(p)
            if h is None:
                continue
            e = self._entries.get(h)
            if e is not None and e[1] > 0:
                e[1] -= 1

    def adopt(self, hashes: list, pages: list) -> list:
        """Transfer ownership of a finished prompt's fresh full pages to
        the cache (refs=1 for the adopting request).  Pages whose hash is
        already cached (a concurrent duplicate prefilled its own copy)
        are NOT adopted — the caller keeps them and they free normally.
        Returns the adopted pages."""
        adopted = []
        self._tick += 1
        for h, p in zip(hashes, pages):
            if h in self._entries or p in self._by_page:
                continue
            self._entries[h] = [p, 1, self._tick]
            self._by_page[p] = h
            adopted.append(p)
        return adopted

    def evict(self, n: int) -> list:
        """Free up to ``n`` pages from refcount-0 entries, LRU first.
        Returns the freed page ids (see ``evict_entries`` for the
        digest-carrying variant the host spill tier feeds on)."""
        return [p for _, p in self.evict_entries(n)]

    def evict_entries(self, n: int) -> list:
        """Free up to ``n`` pages from refcount-0 entries, LRU first;
        returns ``[(digest, page), ...]`` so the caller can demote the
        page CONTENTS to a host tier keyed by the same chain digest a
        future ``match_len`` would look up.
        NOTE: evicting entry i invalidates the hash CHAIN below it for
        future matches, but match_len stops at the first missing digest,
        so correctness holds — later entries just become unreachable and
        age out the same way."""
        if n <= 0:
            return []
        victims = sorted(
            (e for e in self._entries.values() if e[1] == 0),
            key=lambda e: e[2],
        )[:n]
        freed = []
        for e in victims:
            page = e[0]
            h = self._by_page.pop(page)
            del self._entries[h]
            self._states.pop(h, None)
            freed.append((h, page))
        self.evicted_pages += len(freed)
        return freed

    @property
    def stats(self) -> dict:
        return {
            "entries": len(self._entries),
            "pages": len(self._by_page),
            **({"states": len(self._states)} if self.stateful else {}),
            "hits": self.hits,
            "misses": self.misses,
            "evicted_pages": self.evicted_pages,
        }


# ---------------------------------------------------------------------------
# Host-RAM page tier (ISSUE 6): spill instead of die
# ---------------------------------------------------------------------------


def _page_checksum(arrays: dict) -> bytes:
    """Content digest over a page's host buffers, in a fixed field order.
    Spilled int8 pools checksum the raw codes + scale rows, so a
    restore is verified bit-exact in the STORED representation."""
    h = hashlib.blake2b(digest_size=16)
    for field in ("k", "v", "k_scale", "v_scale"):
        a = arrays.get(field)
        if a is not None:
            h.update(np.ascontiguousarray(a).tobytes())
    return h.digest()


def page_checksum(arrays: dict) -> bytes:
    """Public content digest over one page's host buffers (the
    ``gather_pages`` field layout) — the host tier verifies restores
    with it and request snapshots (ISSUE 11) stamp/verify every shipped
    page with the same digest, so a page is checked identically whether
    it crossed a process boundary or just the PCIe bus."""
    return _page_checksum(arrays)


class ColdPageError(RuntimeError):
    """A tiered sequence's demoted cold-middle page failed checksum
    verification (or vanished from the host pool) at stream time.

    Unlike a prefix-cache restore miss — which truncates the chain and
    recomputes, correct by construction — a cold-middle page has no
    recompute path mid-decode: the tokens it holds were already
    conditioned on.  The ONLY safe outcome is a typed failure for this
    request; attending garbage KV would silently corrupt every
    subsequent token."""


class _HostPage:
    """One spilled page: host copies of its K/V (+ int8 scale rows).

    ``arrays`` may still hold device arrays whose host copy is in
    flight (``copy_to_host_async`` issued at spill time — the engine
    thread never blocks on the D2H transfer); ``_finalize`` converts to
    numpy and stamps the checksum on first use."""

    __slots__ = (
        "key", "arrays", "nbytes", "pinned", "tick", "checksum", "ready",
        "device",
    )

    def __init__(self, key, arrays: dict, nbytes: int, pinned: bool,
                 tick: int):
        self.key = key
        self.arrays = arrays
        self.nbytes = nbytes
        self.pinned = pinned
        self.tick = tick
        self.checksum: Optional[bytes] = None
        self.ready = False
        self.device: Optional[dict] = None   # prefetched device handles


class HostPagePool:
    """Byte-budgeted host-RAM tier under the device page pool.

    Two key spaces share one budget:

    - **prefix pages** keyed by the ``PrefixCache`` chain digest:
      ``PrefixCache`` evictions demote here instead of dying, and a
      later admission whose prompt chains onto a host-resident digest
      restores the page into fresh device pages (10-100x the effective
      prefix cache for system-prompt-heavy fleets);
    - **preempted sequences** keyed by ``("seq", request_id, table_pos)``
      and PINNED: a swapped-out decoder's private pages must survive
      until resume or abort, so prefix-spill pressure can never evict
      them.

    Unpinned entries LRU-evict to fit the budget.  Every entry carries a
    content checksum verified at restore (and at prefetch) — a corrupt
    host buffer is detected, dropped, and surfaces as a counter + a
    cache miss (prefix pages) or a resume failure (preempted pages),
    never as silently wrong KV.

    Engine-thread owned; the counters and occupancy ints are plain
    GIL-atomic reads for the /metrics and heartbeat threads.
    """

    def __init__(self, budget_bytes: int):
        self.budget_bytes = int(budget_bytes)
        self._entries: dict = {}
        self._pending: list = []   # keys spilled but not yet finalized
        self._tick = 0
        self._bytes = 0
        # counters (monotonic; scraped as helix_kv_* series)
        self.spilled_pages = 0      # pages demoted device -> host
        self.restored_pages = 0     # pages promoted host -> device
        self.evicted_pages = 0      # unpinned pages LRU-dropped for budget
        self.corrupt_pages = 0      # checksum failures detected at restore
        self.alloc_failures = 0     # spills dropped: budget/fault

    # -- occupancy (GIL-atomic reads, any thread) ---------------------------

    @property
    def used_bytes(self) -> int:
        return self._bytes

    @property
    def pages(self) -> int:
        return len(self._entries)

    @property
    def occupancy(self) -> float:
        return self._bytes / self.budget_bytes if self.budget_bytes else 0.0

    def stats(self) -> dict:
        return {
            "pages": len(self._entries),
            "used_bytes": self._bytes,
            "budget_bytes": self.budget_bytes,
            "spilled_pages": self.spilled_pages,
            "restored_pages": self.restored_pages,
            "evicted_pages": self.evicted_pages,
            "corrupt_pages": self.corrupt_pages,
            "alloc_failures": self.alloc_failures,
        }

    # -- write side (engine thread) -----------------------------------------

    @staticmethod
    def _fault(op: str) -> Optional[dict]:
        from helix_tpu.testing import faults

        inj = faults.active()
        return inj.host_pool_fault(op) if inj is not None else None

    def put(self, key, arrays: dict, pinned: bool = False) -> bool:
        """Adopt one page's buffers (device arrays fresh off a gather, or
        numpy).  Device arrays get ``copy_to_host_async`` issued here so
        the D2H copy overlaps whatever the engine does next; numpy
        conversion + checksum happen lazily on first use.  Returns False
        (and counts ``alloc_failures``) when the page cannot fit."""
        fault = self._fault("spill")
        if fault is not None and fault.get("mode") == "alloc_fail":
            self.alloc_failures += 1
            return False
        nbytes = sum(
            int(a.nbytes) for a in arrays.values() if a is not None
        )
        old = self._entries.get(key)
        if old is not None:
            self._drop(key)
        if nbytes > self.budget_bytes or not self._evict_for(nbytes):
            # a failed RE-spill must not destroy the previously valid
            # host copy (same digest = same content) — put it back; it
            # fit before and only evictions happened since
            if (
                old is not None
                and self._bytes + old.nbytes <= self.budget_bytes
            ):
                self._entries[key] = old
                self._bytes += old.nbytes
            self.alloc_failures += 1
            return False
        for a in arrays.values():
            copy_async = getattr(a, "copy_to_host_async", None)
            if copy_async is not None:
                try:
                    copy_async()
                except Exception:  # noqa: BLE001 — fallback: lazy blocking fetch
                    pass
        self._tick += 1
        self._entries[key] = _HostPage(key, arrays, nbytes, pinned,
                                       self._tick)
        self._bytes += nbytes
        self._pending.append(key)
        self.spilled_pages += 1
        return True

    def drain_pending(self) -> None:
        """Finalize spills whose async D2H copies have had time to land
        (called once per engine step): converts the stored device
        arrays to numpy and stamps checksums, RELEASING the device
        buffers.  Without this, a cold spilled prefix that is never
        re-read would pin its HBM gather buffers for the life of the
        pool — the 'host' tier must not hold device memory beyond ~one
        step."""
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        for key in pending:
            e = self._entries.get(key)
            if e is not None:
                self._finalize(e)

    def _evict_for(self, nbytes: int) -> bool:
        """LRU-drop unpinned entries until ``nbytes`` fit; False when the
        pinned set alone exceeds the headroom."""
        while self._bytes + nbytes > self.budget_bytes:
            victims = [e for e in self._entries.values() if not e.pinned]
            if not victims:
                return False
            victim = min(victims, key=lambda e: e.tick)
            self._drop(victim.key)
            self.evicted_pages += 1
        return True

    def _drop(self, key) -> None:
        e = self._entries.pop(key, None)
        if e is not None:
            self._bytes -= e.nbytes

    def discard(self, key) -> None:
        """Remove an entry without restore accounting (aborted preempted
        request, prefix page superseded on device)."""
        self._drop(key)

    # -- read side (engine thread) ------------------------------------------

    def contains(self, key) -> bool:
        """Presence check only — never blocks on an in-flight D2H copy
        (the admission loop chains digests through this every step)."""
        return key in self._entries

    @staticmethod
    def _finalize(e: _HostPage) -> None:
        if e.ready:
            return
        e.arrays = {
            f: (None if a is None else np.asarray(a))
            for f, a in e.arrays.items()
        }
        e.checksum = _page_checksum(e.arrays)
        e.ready = True

    def get(self, key) -> Optional[dict]:
        """Fetch one page's host buffers for restore, checksum-verified.
        Returns None on a miss OR a detected corruption (the entry is
        dropped and counted — the caller treats it as a cache miss /
        resume failure, never as usable KV)."""
        e = self._entries.get(key)
        if e is None:
            return None
        fault = self._fault("restore")
        if fault is not None:
            if fault.get("mode") == "slow":
                time.sleep(float(fault.get("delay", 0.05)))
            elif fault.get("mode") == "corrupt":
                self._finalize(e)
                k = np.array(e.arrays["k"])   # detached copy, then flip
                k.view(np.uint8).reshape(-1)[0] ^= 0xFF
                e.arrays = {**e.arrays, "k": k}
        self._finalize(e)
        if _page_checksum(e.arrays) != e.checksum:
            self._drop(key)
            self.corrupt_pages += 1
            return None
        self._tick += 1
        e.tick = self._tick
        return e.arrays

    def prefetch(self, key) -> bool:
        """Start the host->device upload for a page expected to restore
        soon (admission saw the digest while the request was still
        queue-blocked): ``jax.device_put`` is async, so the upload
        overlaps the queue wait and the eventual restore consumes the
        in-flight handles.  Verification happens here — a corrupt page
        is dropped now, before any device write."""
        e = self._entries.get(key)
        if e is None:
            return False
        if e.device is not None:
            return True
        arrays = self.get(key)
        if arrays is None:
            return False
        e.device = {
            f: (None if a is None else jax.device_put(a))
            for f, a in arrays.items()
        }
        return True

    def release_device(self, key) -> None:
        """Drop a prefetched entry's device handles (the host copy
        stays).  Prefetch targets HBM — the resource the machine is by
        definition short of when this tier is active — so uploads whose
        admission never materialised (request shed, chain truncated)
        must be let go, not retained until LRU eviction."""
        e = self._entries.get(key)
        if e is not None:
            e.device = None

    def take_restored(self, key) -> Optional[dict]:
        """Claim a page for device restore: verified buffers (device
        handles when prefetched, else host numpy), removed from the pool
        and counted as restored."""
        e = self._entries.get(key)
        if e is None:
            return None
        if e.device is not None:
            out = e.device
        else:
            out = self.get(key)
            if out is None:
                return None
        self._drop(key)
        self.restored_pages += 1
        return out


def gather_pages(cache: PagedKVCache, page_ids: list) -> list:
    """Slice ``page_ids`` out of the device pool as per-page array dicts
    (``[L, page_size, KVH, D]`` each, scale rows ``[L, page_size, KVH]``
    when quantized; a latent pool's page is ``"k" [L, page_size, R +
    128]`` and ``"v"`` None).  One fused gather per field, then cheap per-page
    slices — the result arrays are fresh buffers, safe to hand to
    ``HostPagePool.put`` while later steps donate the pool."""
    idx = jnp.asarray(np.asarray(page_ids, np.int32))
    k = cache.k_pages[:, idx]
    v = None if cache.v_pages is None else cache.v_pages[:, idx]
    ks = vs = None
    if cache.k_scale is not None:
        from helix_tpu.ops.quant import unpack_scale_pages

        ps = cache.k_pages.shape[2]
        ks = unpack_scale_pages(cache.k_scale[:, idx], ps)
        vs = unpack_scale_pages(cache.v_scale[:, idx], ps)
    out = []
    for i in range(len(page_ids)):
        out.append(
            {
                "k": k[:, i],
                "v": None if v is None else v[:, i],
                "k_scale": None if ks is None else ks[:, i],
                "v_scale": None if vs is None else vs[:, i],
            }
        )
    return out


@functools.lru_cache(maxsize=32)
def _build_page_restore_fn(n: int, quantized: bool):
    """One donated scatter writes ``n`` whole pages back into the pool
    (host->device restore).  Cached per (bucketed n, storage mode) so
    restores reuse one executable; padding rows target the garbage
    page 0."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fn(carry, idx, k_new, v_new, k_sc, v_sc):
        k_pages = carry[0].at[:, idx].set(k_new)
        # a latent pool has no second array
        v_pages = None if carry[1] is None else carry[1].at[:, idx].set(
            v_new)
        if not quantized:
            return (k_pages, v_pages)
        return (
            k_pages,
            v_pages,
            carry[2].at[:, idx].set(k_sc),
            carry[3].at[:, idx].set(v_sc),
        )

    return fn


def restore_pages(
    cache: PagedKVCache, page_ids: list, entries: list
) -> PagedKVCache:
    """Write spilled page contents into freshly allocated device pages.

    ``entries[i]`` (from ``HostPagePool.take_restored``) lands in pool
    page ``page_ids[i]``.  The batch is bucketed to a power of two
    (bounded compile shapes, same scheme as chunked prefill) and written
    by ONE donated scatter; prefetched device handles upload nothing
    here — ``jnp.stack`` just fuses the already-resident pages."""
    if not page_ids:
        return cache
    n = len(page_ids)
    bucket = 1
    while bucket < n:
        bucket *= 2
    idx = np.zeros((bucket,), np.int32)   # padding targets garbage page 0
    idx[:n] = page_ids
    quantized = cache.quantized

    def stack(field):
        parts = [e[field] for e in entries]
        parts += [jnp.zeros_like(parts[0])] * (bucket - n)
        return jnp.stack(parts, axis=1)   # [L, bucket, ...]

    k_new = stack("k")
    v_new = None if cache.v_pages is None else stack("v")
    k_sc = v_sc = None
    if quantized:
        from helix_tpu.ops.quant import pack_scale_pages

        k_sc = pack_scale_pages(stack("k_scale"))
        v_sc = pack_scale_pages(stack("v_scale"))
    fn = _build_page_restore_fn(bucket, quantized)
    carry = fn(cache.carry(), jnp.asarray(idx), k_new, v_new, k_sc, v_sc)
    return PagedKVCache.from_carry(carry, cache.state)
