"""Pallas TPU ragged paged-attention kernel — ONE kernel for every caller.

The Ragged Paged Attention design (PAPERS.md) specialised to this
engine's page pool: a flat query axis carved into per-sequence **rows**
(row → query start/length, KV history length, page-table row), so packed/
chunk prefill, plain decode, the mixed prefill+decode step and
speculative verify are all metadata assignments over one compiled kernel
instead of one trace family per caller.

- Row metadata (``t0``/``q_len``/``hist``/``tables``) and the layer index
  are **scalar-prefetched into SMEM**, so every DMA source address is
  computed before the kernel body runs.
- The pool is ``[L, N, P, KVH, D]``: one ``(layer, page)`` slice is a
  contiguous ``[P, KVH, D]`` block, fetched HBM -> VMEM in ONE
  double-buffered async DMA carrying every kv head.
- The grid walks a list of LIVE query blocks built by the wrapper from
  ``q_len`` (scalar-prefetched ``(row, block)`` pairs), never ``rows x
  blocks``: a program owns one query block of one row and computes all
  ``KVH`` head groups from the same VMEM-resident chunks.  Rows are
  ragged: a decode row is 1 token, a verify row ``1+k`` tokens, a prefill
  row a whole chunk — the grid walks ONLY the blocks, pages and fresh
  keys each row actually uses.
- The block shape follows a static bound on a row's fresh tokens
  (``max_q_len``, the engine's ``Sq``).  A plain decode call (bound 1) is
  one-token blocks: its q, fresh K/V and output are a few hundred KB and
  stay in VMEM for the whole call, and its history comes in chunks of 256
  tokens.  Anything longer is 8-token blocks, which stream their q, their
  output and their fresh keys (128 a DMA) and walk chunks of 128.
- A block's history streams page by page into one of two chunk slots
  while the chunk before it is computed, and the FIRST chunk of the next
  block is started before the last one of this block is computed (the
  grid is sequential, the scratch persists): no program begins by
  waiting out a DMA that nothing overlaps.  The pages of a chunk signal
  one semaphore a pool and are awaited by count.
- Online softmax in fp32 over (a) the row's pages-resident history and
  (b) the row's fresh tokens up to the causal limit.  Fresh K/V arrive
  raw (``k_new``/``v_new`` on the flat token axis) and are attended as
  given; persistence into pages is the caller's separate ``write_kv``
  scatter (the flat one-index scatter that keeps the pool's row-major
  layout — see ``engine/kv_cache.py``).
- **Int8 pools**: history pages stream to VMEM as int8 (half the bf16 HBM
  bytes).  Their f32 scales live in lane-dense page rows ``[L, N, KVH*P]``
  (a ``KVH``-minor pool is padded 16-32x in HBM and Mosaic refuses to
  slice it); the wrapper gathers each row's table of scale rows into a
  ``[R, KVH, tokens]`` slab and the kernel streams one lane-aligned window of
  it per chunk.  Dequantization is on the score side — K's scale
  multiplies the scores, V's the probabilities — so the MXU still sees
  fp32 operands and no scale ever crosses from lanes to sublanes.  Fresh
  tokens are attended at full precision; the write path quantizes
  through the shared codec.
- Scores for ALL heads of a q block come from ONE 128-aligned MXU dot:
  the block-diagonal q layout ``[BQ*H, KVH*D]`` (query head h occupies the
  column block of its kv head) against the chunk buffer viewed flat
  ``[T, KVH*D]`` — no per-head strided slices (the same trick the
  decode-only predecessor kernel used, extended to 8-token q blocks).

Layout contract: rows are disjoint and ascending on the flat axis; rows
may start at ANY offset.  A row's final partial query block writes
garbage into the following flat positions, but the grid iterates blocks
in row order ("arbitrary" = sequential on TPU), so every later row's
program overwrites its own positions afterwards — and the wrapper pads
the flat axis so the LAST row's spill and its last block of fresh keys
land in scratch, never out of bounds.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from helix_tpu.ops.attention import DEFAULT_MASK_VALUE



class UnsupportedKernelGeometry(ValueError):
    """The ragged kernel has no TPU lowering for this head geometry."""


def check_geometry(num_heads: int, num_kv_heads: int, head_dim: int,
                   kv_itemsize: int = 2):
    """Raise :class:`UnsupportedKernelGeometry` for a geometry Mosaic
    refuses (per device: under a ``tp`` mesh pass the per-shard counts).

    - The kernel views a chunk as ``[tokens, KVH*D]`` with each head a
      whole number of 128-lane tiles.  A width that divides the 128 lanes
      (64: LFM2) is served PACKED: the pool stores ``128 / D`` kv heads
      side by side in one tile (``[KVH / pack, 128]``, the same bytes) and
      the dispatcher (``ops/paged.py::pack_heads``) hands the kernel that
      geometry, each query zero-filled over its neighbours' lanes; asked
      for ``[P, 8, 64]`` as it is, Mosaic refuses the chunk's reshape
      ("unsupported shape cast").  A width such as Phi-3-mini's 96, which
      neither divides nor is a multiple of 128, is refused.
    - A page is DMA'd as ``[P, KVH, D]``, and the pool's ``(KVH, D)``
      minor pair is tiled in 32-bit sublane packs: ``KVH`` kv heads of
      ``kv_itemsize`` bytes must fill one (2 heads in bf16, 4 in int8), or
      the pool is padded in HBM and the slice is refused ("must be aligned
      to tiling").  Qwen2-7B at tp=4 (one kv head per chip) is such a
      case.
    """
    why = None
    pack = 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 1
    if pack > 1 and num_kv_heads % pack == 0:
        # what the kernel is handed: ``pack`` kv heads a lane tile
        num_kv_heads, head_dim = num_kv_heads // pack, head_dim * pack
    if head_dim % 128:
        why = ("the head width must be a multiple of the 128 lanes, or "
               "divide them with the kv heads a multiple of 128 / width "
               "(served: 64 and multiples of 128; not served: 96)")
    elif num_heads % num_kv_heads:
        why = "the kv heads must divide the query heads"
    elif num_kv_heads * kv_itemsize < 4:
        why = (
            f"{num_kv_heads} kv head(s) of {kv_itemsize}-byte elements per "
            "device do not fill a 32-bit sublane pack (use a wider KV "
            "dtype or fewer head shards)"
        )
    if why:
        raise UnsupportedKernelGeometry(
            "ragged paged-attention kernel: no TPU lowering for "
            f"{num_heads} query / {num_kv_heads} kv heads of width "
            f"{head_dim} per device: {why}.  Serve this geometry with "
            "attn_backend='reference' explicitly, or extend the kernel."
        )


def _ragged_kernel(
    # scalar prefetch
    brow_ref,    # SMEM [NB] int32 row of each live query block (-1 = none)
    bidx_ref,    # SMEM [NB] int32 block index within its row
    t0_ref,      # SMEM [R] int32 row starts on the flat token axis
    qlen_ref,    # SMEM [R] int32 fresh tokens per row (0 = unused)
    hist_ref,    # SMEM [R] int32 pages-resident history tokens per row
    pt_ref,      # SMEM [R, maxP] int32 page tables
    layer_ref,   # SMEM [1] int32 layer index
    # inputs / outputs / scratch, in this order:
    #   qf, knf, vnf, k_hbm, v_hbm [, ks_hbm, vs_hbm row slabs] | of
    #   | kbuf, vbuf, sems, slot [, ksbuf, vsbuf, ssems]
    #   [, qbuf, knbuf, vnbuf, obuf, fsems, qsem, osem]
    # A one-token block (``bq`` 1: a plain decode call) finds qf/knf/vnf/of
    # whole in VMEM; the 8-token block streams its own through the last
    # group of scratch.
    *refs,
    scale: float,
    page_size: int,
    pages_per_chunk: int,
    kv_heads: int,
    group: int,
    bq: int,
    kb: int,
    quantized: bool,
):
    P, C, KVH, BQ, KB = page_size, pages_per_chunk, kv_heads, bq, kb
    resident = BQ == 1
    n_in = 7 if quantized else 5
    qf, knf, vnf, k_hbm, v_hbm, *scale_slabs = refs[:n_in]
    of, kbuf, vbuf, sems, slot_ref, *rest = refs[n_in:]
    if quantized:
        ks_hbm, vs_hbm = scale_slabs
        ksbuf, vsbuf, ssems, *rest = rest
    if not resident:
        qbuf, knbuf, vnbuf, obuf, fsems, qsem, osem = rest
    b = pl.program_id(0)
    NB = brow_ref.shape[0]
    r = brow_ref[b]
    lyr = layer_ref[0]

    def walk_of(row):
        """(pages, chunks) of a row's pages-resident history."""
        npages = jax.lax.div(hist_ref[row] + P - 1, P)
        return npages, jax.lax.div(npages + C - 1, C)

    pools = ((k_hbm, kbuf), (v_hbm, vbuf))

    def scale_dma(row, ci, slot, go: bool):
        # int8 pools: one [KVH, C*P] lane-aligned window of the row's
        # gathered scale slab a chunk (see the wrapper)
        for j, (src, dst) in enumerate(((ks_hbm, ksbuf), (vs_hbm, vsbuf))):
            cp = pltpu.make_async_copy(
                src.at[row, :, pl.ds(ci * (C * P), C * P)],
                dst.at[slot],
                ssems.at[slot, j],
            )
            cp.start() if go else cp.wait()

    def chunk_dma(row, npages, ci, slot, go: bool):
        """Start (``go``) or wait for chunk ``ci`` of ``row`` in ``slot``:
        one DMA a live page and pool.  A slot's pages of one pool all
        signal one semaphore, so the wait is by count, whichever pages
        came: one wait for each bit of the live page count."""
        if quantized:
            scale_dma(row, ci, slot, go)
        live = jnp.minimum(npages - ci * C, C)
        if go:
            def start_page(c, _):
                page = pt_ref[row, ci * C + c]
                for j, (pool, buf) in enumerate(pools):
                    pltpu.make_async_copy(
                        pool.at[lyr, page], buf.at[slot, c],
                        sems.at[slot, j],
                    ).start()
                return 0

            jax.lax.fori_loop(0, live, start_page, 0)
            return
        bit = 1 << (C.bit_length() - 1)
        while bit:
            @pl.when((live & bit) != 0)
            def _():
                for j, (pool, buf) in enumerate(pools):
                    pltpu.make_async_copy(
                        pool.at[lyr, pl.ds(0, bit)],
                        buf.at[slot, pl.ds(0, bit)],
                        sems.at[slot, j],
                    ).wait()
            bit >>= 1

    def start_first_chunk(row, slot):
        npages, nchunks = walk_of(row)

        @pl.when(nchunks > 0)
        def _():
            chunk_dma(row, npages, 0, slot, True)

    # The first chunk of a block's history is in flight before its program
    # starts: the block before it issued it while it computed its own last
    # chunk (the grid is sequential and the scratch persists), into the
    # slot that ``slot_ref`` hands on.  Block 0 has no one before it.
    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0

        @pl.when(r >= 0)
        def _():
            start_first_chunk(r, 0)

    @pl.when(r >= 0)
    def _program():
        i = bidx_ref[b]
        qlen_r = qlen_ref[r]
        hist_r = hist_ref[r]
        base = t0_ref[r] + i * BQ

        def fresh_dma(j, go: bool):
            # block j of the row's fresh keys and values, KB tokens
            for n, (src, dst) in enumerate(((knf, knbuf), (vnf, vnbuf))):
                cp = pltpu.make_async_copy(
                    src.at[pl.ds(t0_ref[r] + j * KB, KB)], dst, fsems.at[n]
                )
                cp.start() if go else cp.wait()

        if not resident:
            qcp = pltpu.make_async_copy(
                qf.at[pl.ds(base, BQ)], qbuf, qsem
            )
            qcp.start()
            fresh_dma(0, True)      # lands behind the history walk

        npages, nchunks = walk_of(r)
        slot0 = slot_ref[0]
        slot_ref[0] = jax.lax.rem(slot0 + nchunks, 2)
        nxt = brow_ref[jnp.minimum(b + 1, NB - 1)]
        has_next = (b + 1 < NB) & (nxt >= 0)

        def start_next_block(slot):
            @pl.when(has_next)
            def _():
                start_first_chunk(jnp.maximum(nxt, 0), slot)

        @pl.when(nchunks == 0)
        def _():
            start_next_block(slot0)

        if resident:
            q = qf[pl.ds(base, BQ)]
        else:
            qcp.wait()
            q = qbuf[...]
        q = q.astype(jnp.float32)            # [BQ, KVH, group, D]
        D = q.shape[-1]
        H = KVH * group
        RQ = BQ * H                          # q_bd rows

        # Block-diagonal q [BQ*H, KVH*D]: kv head k's query rows occupy
        # the column block of its kv head — ONE MXU dot scores every
        # head of every block token against a flat [T, KVH*D] kv view.
        q_bd_rows = []
        for k in range(KVH):
            blk = q[:, k].reshape(BQ * group, D)   # token-major rows
            row = [jnp.zeros((BQ * group, k * D), jnp.float32)] if k else []
            row.append(blk)
            if k < KVH - 1:
                row.append(
                    jnp.zeros((BQ * group, (KVH - 1 - k) * D), jnp.float32)
                )
            q_bd_rows.append(
                jnp.concatenate(row, axis=1) if len(row) > 1 else row[0]
            )
        q_bd = jnp.concatenate(q_bd_rows, axis=0)   # [BQ*H, KVH*D]
        # token offset of each q_bd row within the block (rows are
        # [kv_head, token, group]-major)
        r_iota = jax.lax.broadcasted_iota(jnp.int32, (RQ, 1), 0)
        tok_of_row = jax.lax.rem(r_iota, BQ * group) // group  # [RQ, 1]
        q_off_row = i * BQ + tok_of_row                         # [RQ, 1]

        def scores(keys):
            return jax.lax.dot_general(
                q_bd, keys.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale

        def online(carry, s, vals, p_scale=None):
            """One online-softmax step over a block of (masked) scores."""
            m_prev, l_prev, acc_prev = carry   # [RQ,1],[RQ,1],[RQ,KVH*D]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            if p_scale is not None:
                p = p * p_scale
            acc_new = acc_prev * alpha + jax.lax.dot_general(
                p, vals, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc_new

        def head_rows(sc):
            # [KVH, T] per-head scale rows -> [RQ, T] in q_bd row order
            return jnp.concatenate(
                [
                    jnp.broadcast_to(sc[k:k + 1], (BQ * group, sc.shape[1]))
                    for k in range(KVH)
                ],
                axis=0,
            )

        # ---- history pages: online softmax over the ragged page walk --
        def body(ci, carry):
            slot = jax.lax.rem(slot0 + ci, 2)

            @pl.when(ci + 1 < nchunks)
            def _():
                chunk_dma(r, npages, ci + 1, 1 - slot, True)

            @pl.when(ci + 1 == nchunks)
            def _():
                start_next_block(1 - slot)

            chunk_dma(r, npages, ci, slot, False)
            k_flat = kbuf[slot].reshape(C * P, KVH * D)
            v_flat = vbuf[slot].reshape(C * P, KVH * D).astype(jnp.float32)
            token0 = ci * C * P
            tok = token0 + jax.lax.broadcasted_iota(
                jnp.int32, (1, C * P), 1
            )
            in_range = tok < hist_r             # [1, T]
            # un-DMA'd buffer regions (pages past this row's history)
            # hold garbage; the softmax weight there is exactly 0, but
            # 0 * NaN still poisons the PV accumulation — zero V
            # explicitly (int8 codes are finite whatever the buffer
            # holds, and their scales come from the wrapper's gather).
            v_flat = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (C * P, 1), 0)
                < hist_r - token0,
                v_flat, 0,
            )
            s = scores(k_flat)                  # [RQ, T]
            if quantized:
                # dequantize on the score side: q_bd row block k only
                # ever meets kv head k, so K's per-(token, head) scale
                # multiplies the scores and V's the probabilities —
                # [RQ, T] lane-dense products instead of a lane->sublane
                # relayout of the scales against [T, KVH*D]
                s = s * head_rows(ksbuf[slot])
            s = jnp.where(in_range, s, DEFAULT_MASK_VALUE)
            return online(
                carry, s, v_flat,
                head_rows(vsbuf[slot]) if quantized else None,
            )

        carry = (
            jnp.full((RQ, 1), -jnp.inf, jnp.float32),
            jnp.zeros((RQ, 1), jnp.float32),
            jnp.zeros((RQ, KVH * D), jnp.float32),
        )
        carry = jax.lax.fori_loop(0, nchunks, body, carry)

        # ---- fresh tokens of this row, KB keys a step (causal) --------
        def fresh_body(j, carry):
            if resident:
                src = pl.ds(t0_ref[r] + j * KB, KB)
                kf, vf = knf[src], vnf[src]
            else:
                @pl.when(j > 0)
                def _():
                    fresh_dma(j, True)

                fresh_dma(j, False)
                kf, vf = knbuf[...], vnbuf[...]
            kf = kf.reshape(KB, KVH * D)
            vf = vf.reshape(KB, KVH * D).astype(jnp.float32)
            kv_off = j * KB + jax.lax.broadcasted_iota(
                jnp.int32, (1, KB), 1
            )                                   # [1, KB]
            # a partial tail block reads the NEXT row's fresh tokens (or
            # flat padding); their softmax weight is exactly 0, but a
            # skipped neighbour row's uninitialized output feeds later
            # layers' projections, so its V here can be NaN — and
            # 0 * NaN still poisons the PV accumulation.  Zero V
            # out-of-row, same guard as the history path.
            vf = jnp.where(
                j * KB + jax.lax.broadcasted_iota(
                    jnp.int32, (KB, 1), 0
                ) < qlen_r,
                vf, 0,
            )
            ok = (kv_off < qlen_r) & (kv_off <= q_off_row)  # [RQ, KB]
            s = jnp.where(ok, scores(kf), DEFAULT_MASK_VALUE)
            return online(carry, s, vf)

        # keys 0 .. last_q - 1 are visible to some query of the block
        last_q = jnp.minimum(i * BQ + BQ, qlen_r)
        m, l, acc = jax.lax.fori_loop(
            0, jax.lax.div(last_q + KB - 1, KB), fresh_body, carry
        )

        # fully-masked q rows (block-tail padding past the row's ragged
        # length) have l == 0; guard the divide so garbage stays finite
        out = acc / jnp.where(l > 0, l, 1.0)    # [RQ, KVH*D]
        for k in range(KVH):                    # extract each head block
            head = out[
                k * BQ * group:(k + 1) * BQ * group,
                k * D:(k + 1) * D,
            ].reshape(BQ, group, D).astype(of.dtype)
            if resident:
                of[pl.ds(base, BQ), k] = head
            else:
                obuf[:, k] = head
        if not resident:
            ocp = pltpu.make_async_copy(
                obuf, of.at[pl.ds(base, BQ)], osem
            )
            ocp.start()
            ocp.wait()


def query_block(max_q_len: int) -> int:
    """Tokens in a query block, from the static bound on a row's fresh
    tokens: a plain decode call's rows are their own one-token blocks."""
    return 1 if max_q_len == 1 else 8


def live_query_blocks(q_len, bq: int, tokens: int):
    """The live query blocks of a call, in row order, for the grid to walk:
    ``(row [NB], index in row [NB])`` of each block, row -1 past the last
    live one.  ``NB`` is the most blocks ``tokens`` flat tokens in these
    rows can make."""
    n_rows = q_len.shape[0]
    NB = min(n_rows, tokens) if bq == 1 else tokens // bq + min(n_rows, tokens)
    nblk = (q_len + bq - 1) // bq
    ends = jnp.cumsum(nblk)
    blk = jnp.arange(NB, dtype=jnp.int32)
    brow = jnp.sum((blk[:, None] >= ends[None, :]).astype(jnp.int32), axis=1)
    brow = jnp.where(blk < ends[-1], jnp.minimum(brow, n_rows - 1), -1)
    return brow, blk - (ends - nblk)[jnp.maximum(brow, 0)]


@functools.partial(
    jax.jit, static_argnames=("scale", "max_q_len", "interpret"))
def ragged_paged_attention_tpu(
    q,            # [T, H, D] flat fresh queries
    k_new,        # [T, KVH, D] fresh K/V, attended raw
    v_new,
    k_pages,      # [L, N, P, KVH, D] — FULL pool (read-only here)
    v_pages,
    layer,        # scalar int32
    t0,           # [R] int32 row starts (ascending, disjoint)
    q_len,        # [R] int32 fresh tokens per row (0 = unused)
    hist,         # [R] int32 history tokens per row
    tables,       # [R, maxP] int32
    *,
    scale: Optional[float] = None,
    max_q_len: Optional[int] = None,
    interpret: bool = False,
    k_scale=None,  # [L, N, KVH*P] f32 — present iff the pool is int8
    v_scale=None,
    **tiered,      # span_lo/span_hi/cold_* — NOT supported in-kernel yet
):
    """Returns ``out [T, H, D]``.  Rows may start at any offset; the
    flat axis is padded internally so partial query blocks never DMA out
    of bounds.  ``max_q_len``, a static bound on any row's fresh tokens
    (default: T), picks the query block (``query_block``): 1 token for a
    plain decode call, else 8.

    Tiered-residency metadata (``span_lo``/``span_hi``/``cold_*`` from
    the streamed cold-middle path) is rejected here: this kernel walks
    only pages-resident history and carries no external ``(m, l, acc)``
    stats, so accepting the arguments and ignoring them would silently
    drop the demoted middle — wrong KV.  The dispatcher in
    ``helix_tpu.ops.paged`` routes tiered calls to the reference path;
    the guard keeps any direct caller honest."""
    if any(v is not None for v in tiered.values()):
        raise NotImplementedError(
            "ragged_paged_attention_tpu: tiered cold-middle attention "
            f"({sorted(k for k, v in tiered.items() if v is not None)}) "
            "is reference-only; dispatch via ragged_paged_attention"
        )
    T, H, D = q.shape
    L, N, P, KVH, _ = k_pages.shape
    R, maxP = tables.shape
    if not interpret:
        check_geometry(H, KVH, D, k_pages.dtype.itemsize)
    group = H // KVH
    BQ = query_block(T if max_q_len is None else min(max_q_len, T))
    # Mosaic tiles the (group, D) minor pair of the q/o blocks: a group
    # that is neither a whole sublane tile nor a power-of-two fraction of
    # one (Qwen2-7B: 28/4 = 7) is refused, so pad it with zero query heads
    # and slice them off the output.  A one-token block's rows a kv head
    # are its group alone, and must fill a sublane tile themselves.
    G = group if BQ == 8 and group in (1, 2, 4) else -(-group // 8) * 8
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    # history tokens a chunk: the one-token block's scores are a few
    # vregs whatever the chunk, so it takes the longer walk a DMA round
    C = min(max(1, (256 if BQ == 1 else 128) // P), maxP)
    quantized = k_scale is not None
    # fresh keys a step: the one-token block's own token in a sublane
    # tile of its neighbours, the 8-token block's in chunks of 128
    KB = 8 if BQ == 1 else 128
    # pad so that neither the last row's final (possibly unaligned,
    # possibly partial) query block nor its last block of fresh keys
    # leaves the flat axis
    Tpad = -(-(T + KB + BQ) // 8) * 8
    tail = ((0, Tpad - T), (0, 0), (0, 0))
    k_new, v_new = jnp.pad(k_new, tail), jnp.pad(v_new, tail)
    qg = jnp.pad(
        q.reshape(T, KVH, group, D), (*tail[:2], (0, G - group), (0, 0)))

    q_len = q_len.astype(jnp.int32)
    brow, bidx = live_query_blocks(q_len, BQ, T)

    kernel = functools.partial(
        _ragged_kernel,
        scale=scale,
        page_size=P,
        pages_per_chunk=C,
        kv_heads=KVH,
        group=G,
        bq=BQ,
        kb=KB,
        quantized=quantized,
    )
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)

    def whole(shape):
        # the same block for every program: fetched before the first,
        # written back after the last
        return pl.BlockSpec(shape, lambda b, *_: (0,) * len(shape))

    vmem_limit = None
    if BQ == 1:
        q_spec = out_spec = whole((Tpad, KVH, G, D))
        new_spec = whole((Tpad, KVH, D))
        # four such blocks, double-buffered, each token's minor pair padded
        # to a tile of at most 16 sublanes: a wide decode batch outgrows
        # the compiler's 16 MiB default
        held = 8 * Tpad * KVH * 16 * D * q.dtype.itemsize
        vmem_limit = min(max(16 << 20, held + (8 << 20)), 100 << 20)
    else:
        q_spec = out_spec = new_spec = any_spec
    in_specs = [q_spec, new_spec, new_spec] + [any_spec] * (
        4 if quantized else 2)
    scratch = [
        pltpu.VMEM((2, C, P, KVH, D), k_pages.dtype),       # kbuf
        pltpu.VMEM((2, C, P, KVH, D), v_pages.dtype),       # vbuf
        pltpu.SemaphoreType.DMA((2, 2)),                    # sems
        pltpu.SMEM((1,), jnp.int32),                        # slot
    ]
    if quantized:
        scratch += [
            pltpu.VMEM((2, KVH, C * P), jnp.float32),       # ksbuf
            pltpu.VMEM((2, KVH, C * P), jnp.float32),       # vsbuf
            pltpu.SemaphoreType.DMA((2, 2)),                # ssems
        ]
    if BQ != 1:
        scratch += [
            pltpu.VMEM((BQ, KVH, G, D), q.dtype),           # qbuf
            pltpu.VMEM((KB, KVH, D), k_new.dtype),          # knbuf
            pltpu.VMEM((KB, KVH, D), v_new.dtype),          # vnbuf
            pltpu.VMEM((BQ, KVH, G, D), q.dtype),           # obuf
            pltpu.SemaphoreType.DMA((2,)),                  # fsems
            pltpu.SemaphoreType.DMA(()),                    # qsem
            pltpu.SemaphoreType.DMA(()),                    # osem
        ]
    inputs = (qg, k_new, v_new, k_pages, v_pages)
    if quantized:
        # The scale pools are lane-dense ``[L, N, KVH*P]`` (head-major in
        # a page).  A page's 16 scales per head are far below the 128-lane
        # DMA granule, so XLA gathers each row's table of page rows here
        # and lays them out ``[R, KVH, tokens]``; the kernel then streams
        # one aligned [KVH, C*P] window per chunk.
        def row_scales(pool):
            rows = pool[layer][tables].reshape(R, maxP, KVH, P)
            rows = rows.transpose(0, 2, 1, 3).reshape(R, KVH, maxP * P)
            return jnp.pad(rows, ((0, 0), (0, 0), (0, -maxP % C * P)))

        inputs += (row_scales(k_scale), row_scales(v_scale))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=brow.shape,
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tpad, KVH, G, D), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit,
        ),
    )(
        brow, bidx,
        t0.astype(jnp.int32),
        q_len,
        hist.astype(jnp.int32),
        tables.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        *inputs,
    )
    return out[:T, :, :group].reshape(T, H, D)
