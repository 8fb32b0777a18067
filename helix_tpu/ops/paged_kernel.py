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
  multiplies the scores, V's the probabilities — so no scale ever crosses
  from lanes to sublanes.  Fresh tokens are attended at full precision;
  the write path quantizes through the shared codec.

THREE FORMS of a program, chosen by ``paged_query_block`` from what a call
can see before it runs (the static bound on a row's fresh tokens
``max_q_len``, the engine's ``Sq``; the query heads a kv head; the rows the
segment can hold and its flat tokens), never from a name or a switch:

1. **One token** (a plain decode call, bound 1).  q, fresh K/V and the
   output of the whole call are a few hundred KB and stay in VMEM; the
   history comes in chunks of 256 tokens.  Scores for ALL heads of the token
   come from ONE 128-aligned MXU dot: the block-diagonal q layout ``[H,
   KVH*D]`` (query head h occupies the column block of its kv head) against
   the chunk buffer viewed flat ``[T, KVH*D]`` — no per-head strided slices.
   Bound by the history's bytes, not by its products.
2. **8 tokens** (rows that can only be short: a verify row of ``1 + k``
   tokens, a wave whose rows share a small bucket).  The same block-diagonal
   dot at ``[8*H, KVH*D]``; the block streams its q, its output and its
   fresh keys (128 a DMA) and walks chunks of 128 tokens.  ``KVH`` times the
   MXU passes a head-by-head product needs, which a few short rows never
   notice and a 512-token chunk row paid 64 times over its whole history.
3. **Long** (a chunk row: ``chunk_query_block`` tokens, 128 at a group of 8
   or under, 64 at 16; a wave's rows the power of two at or above their
   share of the bucket).  ONE KV HEAD AT A TIME (``_long_block``): a kv
   head's queries ``[BQ x group, D]`` against that head's keys of a history
   step ``[1024, D]``, under a ``fori_loop`` over kv heads, the step's pages
   laid out head-major once (a reshape and lane-aligned slices of the
   chunk's ``[tokens, KVH*D]`` view).  No block-diagonal query, no zero
   block multiplied; a 512-token row is 4 to 8 programs and walks its
   history 4 to 8 times.  Operands in the queries' dtype with float32
   accumulation (int8 codes widened exactly), statistics in float32.  The
   group is padded to a sublane tile of 8 (Mistral's 4 too: 128 tokens, not
   the 256 an unpadded group would take).

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
    #   [, qbuf, knbuf, vnbuf, obuf, fsems, qsem, osem
    #    [, qhm, khm, vhm, m_ref, l_ref, acc_ref]]
    # A one-token block (``bq`` 1: a plain decode call) finds qf/knf/vnf/of
    # whole in VMEM; a longer block streams its own through the second
    # group of scratch, and a LONG one (``bq`` over 8: a chunk row's) keeps
    # its queries, a step's keys and values and the softmax's state a kv
    # head in the last.
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
        qbuf, knbuf, vnbuf, obuf, fsems, qsem, osem, *long_scratch = rest
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

        if BQ > 8:
            _long_block(
                i, qlen_r, hist_r, nchunks, slot0, kbuf, vbuf, qbuf, obuf,
                knbuf, vnbuf, *long_scratch,
                ksbuf=ksbuf if quantized else None,
                vsbuf=vsbuf if quantized else None,
                scale=scale, q_copy=qcp, fresh_dma=fresh_dma,
                chunk_dma=functools.partial(chunk_dma, r, npages),
                start_next_block=start_next_block)
            ocp = pltpu.make_async_copy(
                obuf, of.at[pl.ds(base, BQ)], osem
            )
            ocp.start()
            ocp.wait()
            return

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


def _long_block(
    i, qlen_r, hist_r, nchunks, slot0,
    kbuf, vbuf,    # VMEM [2, C, P, KVH, D] a history step's pages, two slots
    qbuf,          # VMEM [BQ, KVH, G, D] the block's queries (in flight)
    obuf,          # VMEM [BQ, KVH, G, D] the block's output
    knbuf, vnbuf,  # VMEM [KB, KVH, D] a step of the row's fresh K/V (step
                   # 0 in flight)
    qhm,           # VMEM [KVH, BQ * G, D] the queries, head-major
    khm, vhm,      # VMEM [KVH, S, D] the step's keys and values, head-major
    m_ref, l_ref,  # VMEM [KVH, BQ * G, 1] the running maximum and sum
    acc_ref,       # VMEM [KVH, BQ * G, D] the unnormalised output
    *, ksbuf, vsbuf, scale, q_copy, fresh_dma, chunk_dma, start_next_block,
):
    """One LONG query block of a chunk row: each kv head's queries ``[BQ x
    group, D]`` meet that head's keys of a step alone, ``[S, D]``, a head at
    a time under a ``fori_loop``: dense products, no block-diagonal query and
    no zero block multiplied.

    The history comes in steps of ``S = C x P`` tokens (one DMA a page into
    one of two slots, the next step in flight while this one is computed,
    the next block's first step started before this block's last: the
    8-token block's walk with a wider step), then the row's fresh keys in
    steps of ``KB``, causal, as far as some query of the block can see.  A
    step's keys are laid out head-major once (``[S, KVH, D] -> [KVH, S,
    D]``: a reshape and lane-aligned slices), which frees the fresh keys'
    buffer for the next step's DMA while this one is computed.  The online
    softmax's state is per kv head, in VMEM across the steps; a step pays
    for its two reductions over the lanes, its ``alpha`` and the
    accumulator's rescale whatever its width (14 us at 1,024 query rows and
    4 kv heads, what 2,000 keys of scores cost: PERF.md section 6, PR 52),
    and for its products by its width whatever it holds, so a step is 1,024
    tokens and not what VMEM would hold.
    Products run in the queries' dtype with float32 accumulation (int8 codes
    widened exactly; their scales multiply the scores and the probabilities
    as one ``[1, S]`` row a head), statistics in float32."""
    KVH, RQ, D = qhm.shape
    BQ, G = qbuf.shape[0], qbuf.shape[2]
    S, KB = khm.shape[1], knbuf.shape[0]
    dot_dtype = qhm.dtype
    # one MXU mode for bf16 operands, whatever the process-wide matmul
    # precision (the flash kernel's note)
    prec = (jax.lax.Precision.DEFAULT if dot_dtype == jnp.bfloat16
            else None)

    def relay(flat, dst, keep=None):
        """``flat [n, KVH * D]`` to ``dst[:, :n]`` (``[KVH, S, D]``); of the
        rows at and past ``keep`` zeros.  A head wider than a lane tile (256:
        Qwen3-Next) is zeroed where it LANDS, and only in a step that holds
        such rows: Mosaic refuses the row mask on the pages' flat view there
        ("changeBitwidth when minor tiling is not 128")."""
        n = flat.shape[0]
        if flat.dtype != dot_dtype:
            flat = flat.astype(jnp.float32)
        late = keep is not None and D > 128
        if keep is not None and not late:
            flat = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) < keep,
                flat, 0)
        for k in range(KVH):
            dst[k, pl.ds(0, n)] = flat[:, k * D:(k + 1) * D].astype(
                dot_dtype)
        if late:
            @pl.when(keep < n)
            def _():
                rows = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) < keep
                for k in range(KVH):
                    dst[k, pl.ds(0, n)] = jnp.where(
                        rows, dst[k, pl.ds(0, n)], 0).astype(dot_dtype)

    def heads(ok, n, ks=None, vs=None):
        """One step of the online softmax, a kv head at a time, over the
        first ``n`` keys and values of ``khm`` / ``vhm``; ``ok`` masks the
        scores."""
        def head(k, _):
            s = jax.lax.dot_general(
                qhm[k], khm[k, pl.ds(0, n)], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            ) * scale                                       # [RQ, n]
            if ks is not None:
                s = s * ks[pl.ds(k, 1)]
            s = jnp.where(ok, s, DEFAULT_MASK_VALUE)
            m_prev = m_ref[k]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_ref[k] = m_new
            l_ref[k] = alpha * l_ref[k] + jnp.sum(p, axis=-1, keepdims=True)
            if vs is not None:
                p = p * vs[pl.ds(k, 1)]
            acc_ref[k] = acc_ref[k] * alpha + jax.lax.dot_general(
                p.astype(dot_dtype), vhm[k, pl.ds(0, n)],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            )
            return 0

        jax.lax.fori_loop(0, KVH, head, 0)

    q_copy.wait()
    # (sliced and reshaped in float32, whose (8, 128) tile a group of 8
    # fills; the dots run in the queries' dtype)
    q = qbuf[...].astype(jnp.float32)                       # [BQ, KVH, G, D]
    for k in range(KVH):
        qhm[k] = q[:, k].reshape(RQ, D).astype(dot_dtype)
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # ---- history pages, S tokens a step --------------------------------
    def body(ci, _):
        slot = jax.lax.rem(slot0 + ci, 2)

        @pl.when(ci + 1 < nchunks)
        def _():
            chunk_dma(ci + 1, 1 - slot, True)

        @pl.when(ci + 1 == nchunks)
        def _():
            start_next_block(1 - slot)

        chunk_dma(ci, slot, False)
        token0 = ci * S
        relay(kbuf[slot].reshape(S, KVH * D), khm)
        # pages past the row's history were never fetched: their scores are
        # masked, but 0 * NaN would still poison the values' product
        relay(vbuf[slot].reshape(S, KVH * D), vhm, keep=hist_r - token0)
        tok = token0 + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        heads(tok < hist_r, S,
              None if ksbuf is None else ksbuf.at[slot],
              None if vsbuf is None else vsbuf.at[slot])
        return 0

    jax.lax.fori_loop(0, nchunks, body, 0)

    # ---- fresh tokens of this row, KB keys a step (causal) -------------
    # keys 0 .. last_q - 1 are visible to some query of the block
    last_q = jnp.minimum(i * BQ + BQ, qlen_r)
    nfresh = jax.lax.div(last_q + KB - 1, KB)
    # query rows are [token, group]-major
    q_off_row = i * BQ + jax.lax.broadcasted_iota(
        jnp.int32, (RQ, 1), 0) // G

    def fresh_body(j, _):
        fresh_dma(j, False)
        relay(knbuf[...].reshape(KB, KVH * D), khm)
        # past the row lie the NEXT row's fresh tokens (or flat padding),
        # which may be NaN: zero V out-of-row
        relay(vnbuf[...].reshape(KB, KVH * D), vhm, keep=qlen_r - j * KB)

        @pl.when(j + 1 < nfresh)
        def _():
            fresh_dma(j + 1, True)      # lands behind this step's products

        kv_off = j * KB + jax.lax.broadcasted_iota(jnp.int32, (1, KB), 1)
        heads((kv_off < qlen_r) & (kv_off <= q_off_row), KB)  # [RQ, KB]
        return 0

    jax.lax.fori_loop(0, nfresh, fresh_body, 0)

    def normalise(k, _):
        # (l >= 1: a row's maximum counts 1, and block-tail padding past the
        # row, which sees no key at all, stays finite)
        out = acc_ref[k] / l_ref[k]                           # [RQ, D]
        obuf[:, k] = out.reshape(BQ, G, D).astype(obuf.dtype)
        return 0

    jax.lax.fori_loop(0, KVH, normalise, 0)


def vmem_scratch(held: list, shape, dtype):
    """A VMEM scratch buffer, its bytes there appended to ``held``: the minor
    pair padded to the dtype's tile (4 kv heads in bf16 are a quarter of a
    (16, 128) one)."""
    size = jnp.dtype(dtype).itemsize
    sub = 8 * (4 // size)
    held.append(math.prod(shape[:-2]) * -(-shape[-2] // sub) * sub
                * -(-shape[-1] // 128) * 128 * size)
    return pltpu.VMEM(shape, dtype)


def query_block(max_q_len: int) -> int:
    """Tokens in a query block of the LATENT kernel (``ops/mla_kernel.py``),
    and of this one where a row cannot be long, from the static bound on a
    row's fresh tokens: a plain decode call's rows are their own one-token
    blocks, anything else is 8-token blocks."""
    return 1 if max_q_len == 1 else 8


# query rows a kv head and product the long block aims at (``BQ x group``):
# a block of keys is the MXU's stationary operand for that many rows
CHUNK_QUERY_ROWS = 1024
# history tokens a step of the long block's walk, and fresh keys a step: a
# step costs its statistics whatever its width and its products by its width
# whatever it holds, so 512 lost to the first and 2,048 to the second at
# histories under it (PERF.md section 6, PR 52)
LONG_STEP_TOKENS = 1024
LONG_FRESH_TOKENS = 512


def chunk_query_block(max_q_len: int, group: int) -> int:
    """Tokens in a chunk row's query block, from the static bound on a row's
    fresh tokens (the bucket) and the query heads a kv head: about
    ``CHUNK_QUERY_ROWS`` query rows a product (128 tokens at a group of 8),
    never more than the bucket holds, a multiple of 8.  A row of ``n`` tokens
    makes ``ceil(n / block)`` blocks, and as many under any bucket that holds
    it (a bucket under the cap is one block)."""
    cap = max(8, CHUNK_QUERY_ROWS // (-(-group // 8) * 8) // 8 * 8)
    return max(8, min(cap, max_q_len // 8 * 8))


def paged_query_block(max_q_len: int, group: int, rows: int,
                      tokens: int) -> int:
    """Tokens in a query block of ``ragged_paged_attention_tpu``, from what
    a call can see before it runs: the static bound on a row's fresh tokens,
    the query heads a kv head, the rows the segment can hold and its flat
    tokens.

    - 1 for a plain decode call (``max_q_len`` 1): the resident form.
    - ``chunk_query_block`` for a segment of ONE row (a prompt's chunk): 128
      tokens at a group of 8 or under (groups are padded to a sublane tile of
      8), 64 at a group of 16, never more than the bucket; under 16 tokens (a
      verify row of ``1 + k``) the 8-token block.
    - a segment of SEVERAL rows shares its tokens between them, so its rows
      cannot all be long: the power of two at or above ``tokens / rows``,
      where that is under the one-row block (a wave of 32 rows in a 64-token
      bucket keeps the 8-token block; 32 rows in a 512-token bucket take 16,
      12 rows 64): such a segment walks at most ``2 x rows`` blocks however
      its tokens are dealt, and a short row does not pay a 128-token block.

    A block over 8 tokens runs the long form (``_long_block``)."""
    if max_q_len == 1:
        return 1
    block = chunk_query_block(max_q_len, group)
    if rows > 1:
        share = -(-tokens // rows)
        block = min(block, max(8, 1 << (share - 1).bit_length()))
    return block


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
    (default: T), picks the query block with the group, the rows and the
    tokens (``paged_query_block``): 1 token for a plain decode call, 8 for
    rows that can only be short, else a chunk row's long block.  Int8 pools
    and packed heads (``ops/paged.py::pack_heads``) take the long block by
    the same form: Mosaic refused neither.

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
    BQ = paged_query_block(
        T if max_q_len is None else min(max_q_len, T), group, R, T)
    long = BQ > 8
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
    if long:
        # the long block's steps: history in ``LONG_STEP_TOKENS`` (no wider
        # than a table's tokens to the lanes' tile), fresh keys in
        # ``LONG_FRESH_TOKENS``
        C = max(1, min(LONG_STEP_TOKENS, -(-maxP * P // 128) * 128) // P)
        KB = min(C * P, LONG_FRESH_TOKENS)
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

    held = []           # (a long block sets its VMEM limit from these)
    vmem = functools.partial(vmem_scratch, held)

    vmem_limit = None
    if BQ == 1:
        q_spec = out_spec = whole((Tpad, KVH, G, D))
        new_spec = whole((Tpad, KVH, D))
        # four such blocks, double-buffered, each token's minor pair padded
        # to a tile of at most 16 sublanes: a wide decode batch outgrows
        # the compiler's 16 MiB default
        resident = 8 * Tpad * KVH * 16 * D * q.dtype.itemsize
        vmem_limit = min(max(16 << 20, resident + (8 << 20)), 100 << 20)
    else:
        q_spec = out_spec = new_spec = any_spec
    in_specs = [q_spec, new_spec, new_spec] + [any_spec] * (
        4 if quantized else 2)
    scratch = [
        vmem((2, C, P, KVH, D), k_pages.dtype),             # kbuf
        vmem((2, C, P, KVH, D), v_pages.dtype),             # vbuf
        pltpu.SemaphoreType.DMA((2, 2)),                    # sems
        pltpu.SMEM((1,), jnp.int32),                        # slot
    ]
    if quantized:
        scratch += [
            vmem((2, KVH, C * P), jnp.float32),             # ksbuf
            vmem((2, KVH, C * P), jnp.float32),             # vsbuf
            pltpu.SemaphoreType.DMA((2, 2)),                # ssems
        ]
    if BQ != 1:
        scratch += [
            vmem((BQ, KVH, G, D), q.dtype),                 # qbuf
            vmem((KB, KVH, D), k_new.dtype),                # knbuf
            vmem((KB, KVH, D), v_new.dtype),                # vnbuf
            vmem((BQ, KVH, G, D), q.dtype),                 # obuf
            pltpu.SemaphoreType.DMA((2,)),                  # fsems
            pltpu.SemaphoreType.DMA(()),                    # qsem
            pltpu.SemaphoreType.DMA(()),                    # osem
        ]
    if long:
        RQ = BQ * G
        scratch += [
            vmem((KVH, RQ, D), q.dtype),                    # qhm
            vmem((KVH, C * P, D), q.dtype),                 # khm
            vmem((KVH, C * P, D), q.dtype),                 # vhm
            vmem((KVH, RQ, 1), jnp.float32),                # m
            vmem((KVH, RQ, 1), jnp.float32),                # l
            vmem((KVH, RQ, D), jnp.float32),                # acc
        ]
        # beside them a head's scores, mask and probabilities, and a step's
        # pages on their way to the head-major layout
        held.append(RQ * C * P * 4 * 4 + 2 * C * P * KVH * D * 4)
        vmem_limit = min(max(16 << 20, sum(held) + (8 << 20)), 100 << 20)
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
