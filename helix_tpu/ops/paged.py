"""Ragged paged attention: ONE op for every device-step caller.

This is the serving engine's only attention over the page pool (SURVEY.md
§7 hard part #1) — the reference gets the decode case from vLLM's
PagedAttention CUDA kernels inside its containers; here the op is
TPU-owned AND generalized the way the Ragged Paged Attention paper
(PAPERS.md) argues for: per-row sequence metadata instead of one compiled
shape per caller.

- ``ragged_paged_attention`` — the dispatcher.  Queries arrive as a flat
  token axis ``[T, H, D]`` carved into up to R **rows** (one row = one
  sequence's fresh tokens this call): ``t0[r]``/``q_len[r]`` delimit row
  r's tokens, ``hist[r]`` is its pages-resident history length, and
  ``tables[r]`` its page-table row.  Every engine caller is a metadata
  assignment over this one contract:

  * plain decode — R slots, ``q_len`` 1 each, ``hist`` = position;
  * speculative verify — ``q_len`` = 1 + drafted tokens (ragged);
  * packed / cache-hit prefill — one row per admitted prompt,
    ``hist`` = its prefix-cache-resident tokens (0 for a cold prompt);
  * chunked prefill — one row, ``q_len`` = chunk, ``hist`` = chunk start;
  * the mixed step — prefill rows and decode rows in the same call.

- ``ragged_paged_attention_reference`` — XLA gather-based oracle: gathers
  each row's pages, masks beyond its history, and runs the plain-softmax
  ``mha_reference`` with segment ids (row identity) + absolute positions
  (causality).  Correct everywhere; bandwidth-wasteful (gathers
  ``max_pages`` per row).
- ``ragged_paged_attention_tpu`` (``helix_tpu/ops/paged_kernel``) — the
  Pallas kernel: walks ONLY the pages each row actually uses (ragged over
  rows), one whole-page ``[P, KVH, D]`` DMA per page, query blocks of 1
  token (plain decode: ``max_q_len`` 1), 8 (rows that can only be short)
  or a chunk row's long block scored one kv head at a time
  (``paged_query_block``), int8 dequantization in-register after the page
  fetch.

- ``paged_decode_attention_reference`` is kept as the decode-shaped
  numerics oracle for tests (one query token per sequence, no fresh-token
  self-attention plumbing).

Semantics shared by both backends:

- token t of row r sits at absolute position ``hist[r] + (t - t0[r])``;
  it attends the row's pages-resident history ``[0, hist[r])`` plus the
  row's fresh tokens up to and including itself (causal).  Fresh K/V are
  attended RAW (as given) — exactly what the pre-unification prefill and
  verify paths did; persistence into pages is the caller's separate
  ``write_kv`` scatter.
- rows never see each other: cross-row attention is masked (the packed-
  prefill segment contract).
- a row with ``q_len[r] == 0`` is unused; tokens outside every row
  produce unspecified output the caller must ignore.
- int8 pools: pass the per-(slot, head) f32 scale pools (``k_scale`` /
  ``v_scale``, lane-dense page rows ``[L, N, KVH*P]``, see
  ``ops.quant.pack_scale_pages``); history dequantizes in-register after
  the page fetch — HBM traffic stays at 1 byte/elem plus the scales.

Layout contract (both backends): ``t0`` is ascending and rows are
disjoint; rows may start at any offset (the Pallas kernel pads the flat
axis internally so its query and fresh-key blocks never DMA out of bounds).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from helix_tpu.ops.attention import (
    DEFAULT_MASK_VALUE,
    head_shards,
    mha_reference,
    over_heads,
    resolve_backend,
)
from helix_tpu.parallel.ring_attention import _merge_stats


def paged_decode_attention_reference(
    q,            # [B, H, D]
    k_pages,      # [N, P, KVH, D] — ONE layer's pages
    v_pages,
    page_tables,  # [B, maxP] int32
    lengths,      # [B] int32 — past tokens in cache
    k_new=None,   # [B, KVH, D] current token's K (logically at slot lengths[b])
    v_new=None,
    *,
    scale: Optional[float] = None,
    k_scale=None,  # [N, P, KVH] f32 — ONE layer's scale pool (int8 pages)
    v_scale=None,
) -> jax.Array:
    B, H, D = q.shape
    N, P, KVH, _ = k_pages.shape
    maxP = page_tables.shape[1]
    group = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    # Gather each sequence's pages: [B, maxP, P, KVH, D] -> [B, KVH, T, D]
    T = maxP * P
    kg = k_pages[page_tables].astype(jnp.float32)
    vg = v_pages[page_tables].astype(jnp.float32)
    if k_scale is not None:
        kg = kg * k_scale[page_tables].astype(jnp.float32)[..., None]
        vg = vg * v_scale[page_tables].astype(jnp.float32)[..., None]
    kg = kg.reshape(B, T, KVH, D).transpose(0, 2, 1, 3)
    vg = vg.reshape(B, T, KVH, D).transpose(0, 2, 1, 3)
    valid = jnp.arange(T)[None, :] < lengths[:, None]  # [B, T]
    if k_new is not None:
        kg = jnp.concatenate(
            [kg, k_new[:, :, None, :].astype(jnp.float32)], axis=2
        )
        vg = jnp.concatenate(
            [vg, v_new[:, :, None, :].astype(jnp.float32)], axis=2
        )
        valid = jnp.concatenate([valid, jnp.ones((B, 1), bool)], axis=1)

    qg = q.reshape(B, KVH, group, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bktd->bkgt", qg, kg) * scale
    s = jnp.where(valid[:, None, None, :], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", p, vg)
    return out.reshape(B, H, D).astype(q.dtype)


def _row_of_tokens(t0, q_len, T: int):
    """Per-token row assignment from ascending disjoint row extents.

    Returns ``(row, q_off)``: ``row[t]`` is the owning row id or -1 for
    tokens outside every row; ``q_off[t]`` the token's offset within its
    row (garbage where ``row < 0``)."""
    t = jnp.arange(T)
    # last row whose start is <= t (t0 ascending)
    cand = jnp.sum((t[:, None] >= t0[None, :]).astype(jnp.int32), axis=1) - 1
    cand = jnp.clip(cand, 0, t0.shape[0] - 1)
    start = t0[cand]
    in_row = (t >= start) & (t < start + q_len[cand])
    return jnp.where(in_row, cand, -1), t - start


def _cold_chunk_stats(q, row, cold_k, cold_v, cold_row, cold_len, *,
                      scale, k_scale=None, v_scale=None):
    """Online-softmax stats of the flat queries vs. staged cold chunks.

    ``cold_k``/``cold_v`` are ONE layer's staged cold-middle chunks
    ``[nC, Ct, KVH, D]`` (pool dtype; ``k_scale``/``v_scale`` are the
    matching ``[nC, Ct, KVH]`` scale slabs for int8 pools), ``cold_row``
    the owning flat-axis row per chunk (-1 = padding chunk) and
    ``cold_len`` the valid token count per chunk.  A ``lax.scan`` in
    ascending chunk order folds each chunk's blockwise stats into a
    running ``(m, l, acc)`` with the exact ``ring_attention`` combine —
    the deterministic merge order is what keeps tiered runs reproducible.
    Cold tokens all precede every live query (they are the demoted middle
    of the history), so no causal mask is needed: ownership + chunk
    length decide visibility.  Returns fp32 ``(m [1,H,T,1], l, acc
    [1,H,T,D])``.
    """
    T, H, D = q.shape
    KVH = cold_k.shape[2]
    qf = q[None].astype(jnp.float32)                     # [1, T, H, D]
    acc0 = jnp.zeros((1, H, T, D), jnp.float32)
    l0 = jnp.zeros((1, H, T, 1), jnp.float32)
    m0 = l0 - jnp.inf

    def fold(carry, xs):
        m, l, acc = carry
        ck, cv, crow, clen, cks, cvs = xs                # [Ct, KVH, D], ...
        if cks is not None:
            ck = ck.astype(jnp.float32) * cks[..., None]
            cv = cv.astype(jnp.float32) * cvs[..., None]
        ck = ck.astype(q.dtype)
        cv = cv.astype(q.dtype)
        if KVH != H:
            ck = jnp.repeat(ck, H // KVH, axis=1)
            cv = jnp.repeat(cv, H // KVH, axis=1)
        s = jnp.einsum(
            "bqhd,bkhd->bhqk",
            qf,
            ck[None].astype(jnp.float32),
        ) * scale
        ok = (row[:, None] == crow) & (row[:, None] >= 0)  # [T, 1]
        ok = ok & (jnp.arange(ck.shape[0])[None, :] < clen)
        s = jnp.where(ok[None, None], s, DEFAULT_MASK_VALUE)
        bm = jnp.max(s, axis=-1, keepdims=True)
        bp = jnp.exp(s - bm)
        bl = jnp.sum(bp, axis=-1, keepdims=True)
        bacc = jnp.einsum(
            "bhqk,bkhd->bhqd", bp, cv[None].astype(jnp.float32)
        )
        return _merge_stats(m, l, acc, bm, bl, bacc), None

    xs = (cold_k, cold_v, cold_row, cold_len, k_scale, v_scale)
    (m, l, acc), _ = jax.lax.scan(fold, (m0, l0, acc0), xs)
    return m, l, acc


def ragged_paged_attention_reference(
    q,            # [T, H, D] flat fresh queries
    k_new,        # [T, KVH, D] fresh K/V, attended raw
    v_new,
    k_pages,      # [L, N, P, KVH, D] — FULL pool
    v_pages,
    layer,        # scalar int32 — which layer's pages to read
    t0,           # [R] int32 — row r's first flat token (ascending)
    q_len,        # [R] int32 — row r's fresh-token count (0 = unused)
    hist,         # [R] int32 — row r's pages-resident history tokens
    tables,       # [R, maxP] int32 — row r's page table
    *,
    scale: Optional[float] = None,
    k_scale=None,  # [L, N, KVH*P] f32 — int8 pools' scale page rows
    v_scale=None,
    span_lo=None,  # [R] int32 — first cold (non-resident) history token
    span_hi=None,  # [R] int32 — one past the last cold history token
    cold_k=None,   # [L, nC, Ct, KVH, D] staged cold-middle chunks
    cold_v=None,
    cold_row=None,     # [nC] int32 — owning row per chunk (-1 = padding)
    cold_len=None,     # [nC] int32 — valid tokens per chunk
    cold_k_scale=None,  # [L, nC, Ct, KVH] f32 — int8 chunk scales
    cold_v_scale=None,
) -> jax.Array:
    """XLA oracle for the ragged contract: gather every row's pages, build
    one segment-masked kv axis (R histories + the fresh tokens) and run
    the plain-softmax oracle.  Numerics match the pre-unification callers:
    history dequantized then cast to the compute dtype, fresh K/V raw,
    masked positions at ``DEFAULT_MASK_VALUE`` (``exp`` → exactly 0.0, so
    the gather's fixed ``maxP`` width cannot perturb live sums).

    Tiered KV residency (``span_lo``/``span_hi`` + ``cold_*``): row r's
    history tokens in ``[span_lo[r], span_hi[r])`` are NOT pages-resident
    (their table entries were demoted to the host tier and point at
    garbage) — they are excluded from the hot gather's mask and instead
    attended from the staged cold chunks via the online-softmax
    ``(m, l, acc)`` combine, chunks first in ascending order, then the
    hot+fresh block, so one deterministic merge reproduces the monolithic
    masked softmax over the identical values.  ``span_lo == span_hi == 0``
    rows are fully resident and unaffected; with no tiered arguments the
    legacy single-softmax path runs byte-identically.
    """
    T, H, D = q.shape
    R, maxP = tables.shape
    _, N, P, KVH, _ = k_pages.shape
    Hs = maxP * P
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    row, q_off = _row_of_tokens(t0, q_len, T)
    q_pos = jnp.where(row >= 0, hist[jnp.clip(row, 0)] + q_off, 0)

    kp_l = k_pages[layer]
    vp_l = v_pages[layer]
    kh = kp_l[tables]                       # [R, maxP, P, KVH, D]
    vh = vp_l[tables]
    if k_scale is not None:
        from helix_tpu.ops.quant import unpack_scale_pages

        ks = unpack_scale_pages(k_scale[layer][tables], P)
        vs = unpack_scale_pages(v_scale[layer][tables], P)
        kh = kh.astype(jnp.float32) * ks[..., None]
        vh = vh.astype(jnp.float32) * vs[..., None]
    kh = kh.astype(q.dtype).reshape(1, R * Hs, KVH, D)
    vh = vh.astype(q.dtype).reshape(1, R * Hs, KVH, D)
    hist_tok = jnp.arange(Hs)
    resident = hist_tok[None, :] < hist[:, None]          # [R, Hs]
    if span_lo is not None:
        cold = (hist_tok[None, :] >= span_lo[:, None]) & (
            hist_tok[None, :] < span_hi[:, None]
        )
        resident = resident & ~cold
    kv_seg_h = jnp.where(
        resident,
        jnp.arange(R)[:, None] + 1,
        0,
    ).reshape(1, R * Hs)
    kv_pos_h = jnp.broadcast_to(hist_tok[None, :], (R, Hs)).reshape(
        1, R * Hs
    )
    k_all = jnp.concatenate([kh, k_new.astype(q.dtype)[None]], axis=1)
    v_all = jnp.concatenate([vh, v_new.astype(q.dtype)[None]], axis=1)
    seg_fresh = jnp.where(row >= 0, row + 1, 0)
    kv_seg = jnp.concatenate([kv_seg_h, seg_fresh[None]], axis=1)
    kv_pos = jnp.concatenate([kv_pos_h, q_pos[None]], axis=1)
    if cold_k is None:
        out = mha_reference(
            q[None], k_all, v_all,
            causal=True,
            q_positions=q_pos[None],
            kv_positions=kv_pos,
            q_segment_ids=seg_fresh[None],
            kv_segment_ids=kv_seg,
            scale=scale,
        )
        return out[0]

    # Streamed path: cold chunk stats first (ascending chunk order), then
    # the hot + fresh block's stats, one final combine.  Same masked
    # logits as ``mha_reference`` would build for the hot block.
    cm, cl, cacc = _cold_chunk_stats(
        q, row, cold_k[layer], cold_v[layer], cold_row, cold_len,
        scale=scale,
        k_scale=None if cold_k_scale is None else cold_k_scale[layer],
        v_scale=None if cold_v_scale is None else cold_v_scale[layer],
    )
    kf = k_all if k_all.shape[2] == H else jnp.repeat(
        k_all, H // KVH, axis=2
    )
    vf = v_all if v_all.shape[2] == H else jnp.repeat(
        v_all, H // KVH, axis=2
    )
    s = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q[None].astype(jnp.float32),
        kf.astype(jnp.float32),
    ) * scale
    mask = q_pos[None][:, None, :, None] >= kv_pos[:, None, None, :]
    mask = mask & (
        seg_fresh[None][:, None, :, None] == kv_seg[:, None, None, :]
    )
    s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    hm = jnp.max(s, axis=-1, keepdims=True)
    hp = jnp.exp(s - hm)
    hl = jnp.sum(hp, axis=-1, keepdims=True)
    hacc = jnp.einsum("bhqk,bkhd->bhqd", hp, vf.astype(jnp.float32))
    m, l, acc = _merge_stats(cm, cl, cacc, hm, hl, hacc)
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l).transpose(0, 2, 1, 3)                 # [1, T, H, D]
    return out[0].astype(q.dtype)


def pack_heads(q, k_new, v_new, pack: int):
    """A call at head width ``D = 128 / pack`` as the same attention at
    width 128 over ``KVH / pack`` kv heads: ``pack`` neighbouring kv heads
    share a 128-lane tile (``[T, KVH, D]`` viewed ``[T, KVH / pack, 128]``:
    the same values in the same order, which is how the pool stores them),
    and each query head is zero-filled over the lanes of its kv head's
    neighbours, so its scores see its own kv head alone.  The output then
    holds, for each query head, every neighbour's values under its own
    weights; ``unpack_heads`` keeps its own."""
    T, H, D = q.shape
    KVH = k_new.shape[1]
    group = H // KVH
    # query head h belongs to kv head h // group, lane block (h // group)
    # % pack of packed kv head h // (group * pack)
    block = (jnp.arange(H) // group) % pack                       # [H]
    onehot = jax.nn.one_hot(block, pack, dtype=q.dtype)           # [H, pack]
    qp = (q[:, :, None, :] * onehot[None, :, :, None]).reshape(
        T, H, pack * D)
    shape = (T, KVH // pack, pack * D)
    return qp, k_new.reshape(shape), v_new.reshape(shape)


def unpack_heads(out, pack: int, kv_heads: int):
    """``[T, H, pack * D]`` of a packed call -> ``[T, H, D]``: each query
    head's own lane block."""
    T, H, W = out.shape
    D = W // pack
    block = (jnp.arange(H) // (H // kv_heads)) % pack
    return jnp.take_along_axis(
        out.reshape(T, H, pack, D), block[None, :, None, None], axis=2
    )[:, :, 0]


def ragged_paged_attention(
    q,            # [T, H, D] flat fresh queries across all rows
    k_new,        # [T, KVH, D] fresh K/V (attended raw; caller persists)
    v_new,
    k_pages,      # [L, N, P, KVH, D] — FULL pool
    v_pages,
    layer,        # scalar int32
    t0,           # [R] int32 — row starts (ascending; 8-aligned on pallas)
    q_len,        # [R] int32 — fresh tokens per row (0 = unused row)
    hist,         # [R] int32 — pages-resident history tokens per row
    tables,       # [R, maxP] int32
    *,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
    mesh=None,
    max_q_len: Optional[int] = None,
    k_scale=None,  # [L, N, KVH*P] f32 — int8 pools' scale page rows
    v_scale=None,
    span_lo=None,  # [R] tiered rows: cold history span start (tokens)
    span_hi=None,
    cold_k=None,   # [L, nC, Ct, KVH, D] staged cold-middle chunks
    cold_v=None,
    cold_row=None,
    cold_len=None,
    cold_k_scale=None,
    cold_v_scale=None,
):
    """THE paged-attention entry point: every device-step caller (packed/
    chunk prefill, decode, mixed, spec-verify) is a metadata assignment
    over this one contract.  Returns ``out [T, H, D]``.

    Dispatcher (``ops.attention.resolve_backend``): the Pallas kernel on
    a TPU, the XLA gather oracle on a CPU or for ``backend="reference"``;
    on a TPU the kernel runs or the call raises.  ``mesh``: the mesh the
    heads are sharded over, so that the kernel runs per head shard.
    ``max_q_len`` is a static bound on a row's fresh tokens (1 for plain
    decode), which lets the kernel size its query blocks.
    One documented exception: tiered-residency metadata (``span_lo``/
    ``cold_*``) routes to the reference path on every backend — the
    Pallas kernel walks resident pages only and has no carried-stats
    entry point yet, and silently dropping the cold middle would be wrong
    KV.  That route is off the main serving path (``ctx_hot_pages`` > 0
    only).
    """
    tiered = cold_k is not None or span_lo is not None
    backend = resolve_backend(backend)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    pack = k_pages.shape[-1] // q.shape[-1]
    if pack > 1:
        # a pool of packed heads (head width under 128): the same call at
        # the pool's geometry, on either backend
        if tiered or k_scale is not None:
            raise NotImplementedError(
                "a page pool of packed kv heads has neither an int8 "
                "storage nor tiered residency")
        qp, kp, vp = pack_heads(q, k_new, v_new, pack)
        out = ragged_paged_attention(
            qp, kp, vp, k_pages, v_pages, layer, t0, q_len, hist, tables,
            scale=scale, backend=backend, mesh=mesh, max_q_len=max_q_len)
        return unpack_heads(out, pack, k_new.shape[1])
    if backend == "pallas" and not tiered:
        from helix_tpu.ops.paged_kernel import ragged_paged_attention_tpu

        quantized = k_scale is not None

        def call(q, k_new, v_new, k_pages, v_pages, meta, scales):
            ks, vs = scales if quantized else (None, None)
            return ragged_paged_attention_tpu(
                q, k_new, v_new, k_pages, v_pages, *meta, scale=scale,
                max_q_len=max_q_len, k_scale=ks, v_scale=vs,
            )

        if head_shards(mesh) > 1:
            heads = PartitionSpec(None, "tp", None)
            pool = PartitionSpec(None, None, None, "tp", None)
            scales = PartitionSpec(None, None, "tp")
            call = over_heads(
                call, mesh,
                (heads, heads, heads, pool, pool, PartitionSpec(), scales),
                heads,
            )
        return call(
            q, k_new, v_new, k_pages, v_pages,
            (layer, t0, q_len, hist, tables),
            (k_scale, v_scale) if quantized else (),
        )
    return ragged_paged_attention_reference(
        q, k_new, v_new, k_pages, v_pages, layer, t0, q_len, hist,
        tables, scale=scale, k_scale=k_scale, v_scale=v_scale,
        span_lo=span_lo, span_hi=span_hi,
        cold_k=cold_k, cold_v=cold_v,
        cold_row=cold_row, cold_len=cold_len,
        cold_k_scale=cold_k_scale, cold_v_scale=cold_v_scale,
    )


# ---------------------------------------------------------------------------
# Latent (MLA) pools: one cached vector a token, shared by every head
# ---------------------------------------------------------------------------


def mla_attention_reference(q, c, k_pe, *, q_positions, kv_positions,
                            q_segment_ids, kv_segment_ids, scale=1.0):
    """Absorbed-form latent attention, plain softmax, in f32.

    ``q [Tq, H, R + dr]`` (absorbed query | rope query), ``c [Tk, R]`` the
    normed latents, ``k_pe [Tk, dr]`` the shared rope keys: every head
    scores against the same ``[c | k_pe]`` and the values are ``c``.
    Token t sees key s iff the segment ids agree (non-zero) and
    ``kv_positions[s] <= q_positions[t]``.  Returns ``[Tq, H, R]``."""
    R = c.shape[-1]
    qf = q.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    s = (
        jnp.einsum("qhr,kr->hqk", qf[..., :R], cf)
        + jnp.einsum("qhd,kd->hqk", qf[..., R:], k_pe.astype(jnp.float32))
    ) * scale
    mask = (q_positions[:, None] >= kv_positions[None, :]) & (
        q_segment_ids[:, None] == kv_segment_ids[None, :]
    ) & (kv_segment_ids[None, :] > 0)
    s = jnp.where(mask[None], s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask[None], jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("hqk,kr->qhr", p / jnp.where(l > 0, l, 1.0), cf)
    return out.astype(q.dtype)


def mla_ragged_paged_attention_reference(
    q, c_new, r_new, kv_pages, layer, t0, q_len, hist, tables, *,
    scale: float = 1.0,
):
    """XLA gather oracle of ``mla_ragged_paged_attention``: gathers each
    row's pages, splits a cached row into its latent and its rope key,
    masks beyond the row's history, one plain softmax."""
    T = q.shape[0]
    R, maxP = tables.shape
    P = kv_pages.shape[2]
    lat, dr = c_new.shape[-1], r_new.shape[-1]
    Hs = maxP * P
    row, q_off = _row_of_tokens(t0, q_len, T)
    q_pos = jnp.where(row >= 0, hist[jnp.clip(row, 0)] + q_off, 0)
    kvh = kv_pages[layer][tables].reshape(R * Hs, -1)
    ch, rh = kvh[:, :lat], kvh[:, lat:lat + dr]
    hist_tok = jnp.arange(Hs)
    seg_h = jnp.where(
        hist_tok[None, :] < hist[:, None], jnp.arange(R)[:, None] + 1, 0
    ).reshape(R * Hs)
    pos_h = jnp.broadcast_to(hist_tok[None, :], (R, Hs)).reshape(R * Hs)
    seg_fresh = jnp.where(row >= 0, row + 1, 0)
    return mla_attention_reference(
        q,
        jnp.concatenate([ch.astype(q.dtype), c_new.astype(q.dtype)]),
        jnp.concatenate([rh.astype(q.dtype), r_new.astype(q.dtype)]),
        q_positions=q_pos,
        kv_positions=jnp.concatenate([pos_h, q_pos]),
        q_segment_ids=seg_fresh,
        kv_segment_ids=jnp.concatenate([seg_h, seg_fresh]),
        scale=scale,
    )


def mla_ragged_paged_attention(
    q,            # [T, H, R + dr] absorbed | rope queries, flat over rows
    c_new,        # [T, R] fresh normed latents (attended raw)
    r_new,        # [T, dr] fresh rope keys
    kv_pages,     # [L, N, P, R + 128] latent pool (``PagedKVCache.k_pages``)
    layer, t0, q_len, hist, tables,
    *,
    scale: float = 1.0,
    backend: Optional[str] = None,
    max_q_len: Optional[int] = None,
):
    """Ragged paged attention over a LATENT pool, under the row contract of
    ``ragged_paged_attention`` (decode, chunk with history, mixed, verify
    and cold rows are metadata): H query heads against one cached
    ``[c | k_pe]`` a token, the values a view of the keys (``c``).  The
    pool is ONE array: a token's row holds its normed latent in lanes
    ``0..R`` and its rope key behind it, zero-padded to 128 lanes
    (``mixers.py::latent_widths``), so a page of a layer is one contiguous
    block and one DMA.  The fresh tokens come as the model makes them, the
    latent and the rope key apart (``write_kv`` joins them into rows).
    Returns the attended latents ``[T, H, R]``.  ``max_q_len`` is a static
    bound on a row's fresh tokens (1 for plain decode), which lets the
    kernel size its query blocks.  The Pallas kernel on a TPU, the gather
    oracle on a CPU or for ``backend="reference"``."""
    if resolve_backend(backend) == "pallas":
        from helix_tpu.ops.mla_kernel import mla_ragged_paged_attention_tpu

        return mla_ragged_paged_attention_tpu(
            q, c_new, r_new, kv_pages, layer, t0, q_len, hist, tables,
            scale=scale, max_q_len=max_q_len,
        )
    return mla_ragged_paged_attention_reference(
        q, c_new, r_new, kv_pages, layer, t0, q_len, hist, tables,
        scale=scale,
    )


# ---------------------------------------------------------------------------
# Sparse latent attention behind an indexer (DeepSeek Sparse Attention): the
# plain form of each of ``ops/dsa.py``'s two dense passes
# ---------------------------------------------------------------------------


def dsa_index_scores_reference(q, w, keys):
    """``I[r, t, s] = sum_j w[t, j] relu(q[t, j] . keys[r, s])`` in float32.

    ``q [Rq, T, Hi, Di]`` the index queries, ``w [Rq, T, Hi]`` the heads'
    weights (scales folded in), ``keys [R, S, Di]`` ONE index key a token; ``Rq``
    is ``R`` (a row's own queries: decode) or 1 (every row scores the same
    queries: a chunk's flat axis).  Returns ``[R, T, S]``."""
    s = jnp.einsum("rthd,rsd->rths", q.astype(jnp.float32),
                   keys.astype(jnp.float32))
    return jnp.einsum("rths,rth->rts", jnp.maximum(s, 0.0),
                      w.astype(jnp.float32))


def mla_sparse_attention_reference(q, kv, bias, latent: int):
    """Absorbed-form latent attention of each query over the keys its
    ``bias`` leaves (0 keeps a key, ``DEFAULT_MASK_VALUE`` drops it), plain
    softmax in float32.

    ``q [Rq, T, H, W]`` in the pool's row layout (absorbed query | rope query
    | zeros), ``kv [R, S, W]`` rows ``[c | k_pe | zeros]``, ``bias [R, T,
    S]``; ``Rq`` is ``R`` or 1 (``dsa_index_scores_reference``).  The values
    are lanes ``0..latent`` of the rows.  A query that keeps no key gets
    zeros.  Returns ``[R, T, H, latent]``."""
    kvf = kv.astype(jnp.float32)
    s = jnp.einsum("rthw,rsw->rths", q.astype(jnp.float32), kvf)
    keep = (bias > 0.5 * DEFAULT_MASK_VALUE)[:, :, None, :]
    s = jnp.where(keep, s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(keep, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("rths,rsw->rthw", p / jnp.where(l > 0, l, 1.0),
                     kvf[..., :latent])
    return out.astype(q.dtype)
