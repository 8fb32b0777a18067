"""Pallas TPU ragged paged attention over a LATENT (MLA) page pool.

The row contract of ``ops/paged_kernel.py`` (flat query axis carved into
rows by ``t0``/``q_len``/``hist``/``tables``; decode, chunk with history,
mixed, verify and cold rows are metadata), for multi-head latent attention
in its absorbed form:

- a token caches ONE row, shared by every head, in ONE array ``[L, N, P,
  R + 128]``: the normed latent ``c`` in lanes ``0..R`` (R = 512) and the
  rope key behind it (the published 64 values lane-padded to 128 with
  zeros).  No head axis (a ``(1, R)`` minor pair would be padded 2x in
  HBM), no V: the values are lanes ``0..R`` of the same row.  A ``(layer,
  page)`` slice is ``P * (R + 128)`` contiguous values (20 KB at page 16):
  ONE DMA;
- the H query heads of a block of ``BQ`` tokens are the rows of ONE
  ``[BQ * H, R + 128]`` operand laid out as the pool's rows are (absorbed
  query | rope query | zeros): scores are one MXU product against the
  chunk's rows, ``q_c . c^T + q_r . r^T`` in one contraction, the output
  ``p . c``;
- MXU operands stay in the pool's dtype (bf16) with f32 accumulation;
  softmax statistics in f32;
- the grid walks a list of LIVE query blocks built by the wrapper from
  ``q_len`` (scalar-prefetched ``(row, block)`` pairs), not ``rows x
  blocks``: a decode step of 64 one-token rows is 64 programs of ``BQ`` 1
  (16 query rows each), a 512-token chunk 64 programs of ``BQ`` 8;
- history streams HBM -> VMEM one DMA a page, double-buffered in chunks of
  ``C`` pages.  The pages of a chunk signal ONE semaphore a buffer slot
  and are awaited by count: one wait for each bit of the chunk's live page
  count (one for a full chunk), whichever pages came.  Issuing is what
  paces the walk: the scalar core that starts and awaits the copies also
  drives the vector work, and a page's bytes take less time than the two
  starts and two waits a two-array pool cost (PERF.md section 6, PR 40);
- fresh tokens come from the flat ``[c_new | r_new]`` rows (joined and
  lane-padded by the wrapper) in blocks of ``KB`` keys, one DMA a block,
  attended raw (persisting them is the caller's ``write_kv``).

Behind a sparse-attention indexer (``ModelConfig.is_dsa``) this kernel serves
the rows that attend ALL they have: a cold row of no more fresh tokens than
``index_topk``, with the rope key's lanes of the fresh ``[k_pe | k_idx]`` cut
off by the caller.  A row with history, or past ``index_topk`` keys, goes to
``ops/dsa.py`` (scores over the index-key pool beside this one, a choice a
query, ``ops/dsa_kernel.py``'s dense kernels over gathered rows); the page
table such a model prefetches to SMEM here is up to 1,056 wide.

Layout contract: as the dense kernel's.  Rows are disjoint and ascending;
a row's last partial block spills garbage into the following flat
positions, which a later row's own block overwrites (the grid is
sequential), and the flat axis is padded so that no DMA leaves it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from helix_tpu.ops.attention import DEFAULT_MASK_VALUE
from helix_tpu.ops.paged_kernel import (
    UnsupportedKernelGeometry,
    live_query_blocks,
    query_block,
)

ROPE_LANES = 128   # the rope key's width in the pool and in flight


def check_mla_geometry(num_heads: int, latent: int, rope: int,
                       itemsize: int = 2):
    """Raise :class:`UnsupportedKernelGeometry` for what Mosaic refuses:
    a latent that is not whole 128-lane tiles, a rope key wider than its
    128-lane slot, or query heads that do not fill the sublane tile of
    their dtype (the one-token decode block is ``[H, R + 128]``)."""
    why = None
    if latent % 128:
        why = "the latent width must be a multiple of the 128 lanes"
    elif rope > ROPE_LANES:
        why = f"the rope key must fit its {ROPE_LANES}-lane slot"
    elif num_heads % (32 // itemsize):
        why = (f"{num_heads} query heads of {itemsize}-byte elements do not "
               "fill whole sublane tiles")
    if why:
        raise UnsupportedKernelGeometry(
            "latent ragged paged-attention kernel: no TPU lowering for "
            f"{num_heads} heads over a latent of {latent} + rope {rope}: "
            f"{why}.  Serve this geometry with attn_backend='reference' "
            "explicitly, or extend the kernel."
        )




def _mla_kernel(
    # scalar prefetch
    brow_ref,    # SMEM [NB] int32 row of each query block (-1 = none)
    bidx_ref,    # SMEM [NB] int32 block index within its row
    t0_ref,      # SMEM [R] int32 row starts on the flat token axis
    qlen_ref,    # SMEM [R] int32 fresh tokens per row
    hist_ref,    # SMEM [R] int32 pages-resident history tokens per row
    pt_ref,      # SMEM [R, maxP] int32 page tables
    layer_ref,   # SMEM [1] int32 layer index
    # inputs (HBM): queries, fresh rows, the pool: all [.., R + 128] wide
    qf, knf, kv_hbm,
    # output (HBM)
    o_hbm,
    # scratch
    qbuf, kvbuf, knbuf, obuf, sems, fsem, qsem, osem,
    *,
    scale: float,
    page_size: int,
    pages_per_chunk: int,
    bq: int,
    kb: int,
):
    b = pl.program_id(0)
    r = brow_ref[b]
    P, C, BQ, KB = page_size, pages_per_chunk, bq, kb

    @pl.when(r >= 0)
    def _program():
        i = bidx_ref[b]
        lyr = layer_ref[0]
        qlen_r = qlen_ref[r]
        hist_r = hist_ref[r]
        base = t0_ref[r] + i * BQ
        qcp = pltpu.make_async_copy(qf.at[pl.ds(base, BQ)], qbuf, qsem)
        qcp.start()

        npages = jax.lax.div(hist_r + P - 1, P)
        nchunks = jax.lax.div(npages + C - 1, C)

        def start_chunk(ci, slot):
            # one DMA a live page; a slot's pages all signal its semaphore
            live = npages - ci * C
            for c in range(C):  # static unroll over a chunk's pages
                @pl.when(c < live)
                def _():
                    pltpu.make_async_copy(
                        kv_hbm.at[lyr, pt_ref[r, ci * C + c]],
                        kvbuf.at[slot, c], sems.at[slot],
                    ).start()

        def wait_chunk(ci, slot):
            # by count, whichever pages came: a wait for each bit of the
            # live page count takes that many pages' bytes off the slot's
            # semaphore (a full chunk is one wait)
            live = jnp.minimum(npages - ci * C, C)
            bit = 1 << (C.bit_length() - 1)
            while bit:
                @pl.when((live & bit) != 0)
                def _():
                    pltpu.make_async_copy(
                        kv_hbm.at[lyr, pl.ds(0, bit)],
                        kvbuf.at[slot, pl.ds(0, bit)], sems.at[slot],
                    ).wait()
                bit >>= 1

        @pl.when(nchunks > 0)
        def _():
            start_chunk(0, 0)

        qcp.wait()
        H = qbuf.shape[1]
        W = kvbuf.shape[-1]
        R = W - ROPE_LANES
        RQ = BQ * H
        q2 = qbuf[...].reshape(RQ, W)                 # token-major rows
        # query offset in the row of each q2 row
        q_off = i * BQ + jax.lax.broadcasted_iota(
            jnp.int32, (RQ, 1), 0) // H

        # operands go to the MXU as they are stored (bf16), whatever
        # jax_default_matmul_precision says: Mosaic has no fp32-precision
        # product of bf16 operands
        def online(carry, rows, ok):
            """One online-softmax step over a block of cached rows ``[keys,
            R + 128]``: scores against the whole row (latent | rope key),
            values its latent lanes."""
            m_prev, l_prev, acc_prev = carry
            s = jax.lax.dot_general(
                q2, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT,
            )
            s = jnp.where(ok, s * scale, DEFAULT_MASK_VALUE)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc_prev * alpha + jax.lax.dot_general(
                p.astype(rows.dtype), rows[:, :R], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT,
            )
            return m_new, l_new, acc

        # ---- history pages: the ragged page walk ----------------------
        def hist_body(ci, carry):
            slot = jax.lax.rem(ci, 2)

            @pl.when(ci + 1 < nchunks)
            def _():
                start_chunk(ci + 1, jax.lax.rem(ci + 1, 2))

            wait_chunk(ci, slot)
            left = hist_r - ci * C * P
            # pages past the row's history were never fetched: the buffer
            # there holds whatever it held.  Their softmax weight is
            # exactly 0, but 0 * NaN poisons the PV product: zero them.
            rows = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (C * P, 1), 0) < left,
                kvbuf[slot].reshape(C * P, W), 0)
            ok = jax.lax.broadcasted_iota(
                jnp.int32, (1, C * P), 1) < left
            return online(carry, rows, ok)

        carry = (
            jnp.full((RQ, 1), -jnp.inf, jnp.float32),
            jnp.zeros((RQ, 1), jnp.float32),
            jnp.zeros((RQ, R), jnp.float32),
        )
        carry = jax.lax.fori_loop(0, nchunks, hist_body, carry)

        # ---- the row's fresh tokens, KB keys a block (causal) ----------
        # The flat array is tiled in rows: a DMA may only start on a tile
        # (16 rows of bf16).  So key blocks start at the tile below the
        # row's start, and what lies before the row is masked like what
        # lies after it.
        align = 16
        start = jax.lax.div(t0_ref[r], align) * align
        shift = t0_ref[r] - start

        def fresh_body(j, carry):
            src = pl.multiple_of(start + j * KB, align)
            cp = pltpu.make_async_copy(knf.at[pl.ds(src, KB)], knbuf, fsem)
            cp.start()
            cp.wait()
            # offset in the row of each key of the block (negative before
            # the row); a block's ends read the neighbouring rows' tokens
            # or the flat padding: masked, and zeroed for the PV product
            k_off = j * KB - shift + jax.lax.broadcasted_iota(
                jnp.int32, (KB, 1), 0)
            rows = jnp.where((k_off >= 0) & (k_off < qlen_r), knbuf[...], 0)
            kv_off = j * KB - shift + jax.lax.broadcasted_iota(
                jnp.int32, (1, KB), 1)
            ok = (kv_off >= 0) & (kv_off < qlen_r) & (kv_off <= q_off)
            return online(carry, rows, ok)

        last_q = jnp.minimum(i * BQ + BQ, qlen_r)     # keys 0..last_q-1
        m, l, acc = jax.lax.fori_loop(
            0, jax.lax.div(shift + last_q + KB - 1, KB), fresh_body, carry)

        # block-tail rows past the row's ragged length have l == 0
        out = acc / jnp.where(l > 0, l, 1.0)
        obuf[...] = out.reshape(BQ, H, R).astype(obuf.dtype)
        ocp = pltpu.make_async_copy(obuf, o_hbm.at[pl.ds(base, BQ)], osem)
        ocp.start()
        ocp.wait()


@functools.partial(
    jax.jit, static_argnames=("scale", "max_q_len", "interpret"))
def mla_ragged_paged_attention_tpu(
    q,            # [T, H, R + dr] flat queries: absorbed | rope
    c_new,        # [T, R] fresh latents, attended raw
    r_new,        # [T, dr] fresh rope keys
    kv_pages,     # [L, N, P, R + 128] full latent pool (read-only here)
    layer, t0, q_len, hist, tables,
    *,
    scale: float = 1.0,
    max_q_len: Optional[int] = None,
    interpret: bool = False,
):
    """Returns the attended latents ``[T, H, R]``.  ``max_q_len``, a static
    bound on any row's fresh tokens (default: T), picks the query block:
    1 token for plain decode, else 8."""
    T, H, DQ = q.shape
    L, N, P, W = kv_pages.shape
    R = W - ROPE_LANES
    dr = r_new.shape[-1]
    n_rows, maxP = tables.shape
    dt = kv_pages.dtype
    if not interpret:
        check_mla_geometry(H, R, dr, dt.itemsize)
    assert DQ == R + dr and c_new.shape[-1] == R
    max_q_len = T if max_q_len is None else min(max_q_len, T)
    BQ = query_block(max_q_len)
    KB = 16 if BQ == 1 else 128
    C = max(1, min(256 // P, maxP))
    pad = ROPE_LANES - dr
    # the flat axis grows by a key block and a query block, so neither
    # the last row's fresh-key DMA nor its partial query block leaves it;
    # queries and fresh rows take the pool's row layout (zeros behind the
    # rope lanes score nothing)
    Tpad = -(-(T + KB + BQ) // 16) * 16
    qp = jnp.pad(q, ((0, Tpad - T), (0, 0), (0, pad))).astype(dt)
    kn = jnp.pad(
        jnp.concatenate([c_new.astype(dt), r_new.astype(dt)], axis=-1),
        ((0, Tpad - T), (0, pad)))

    q_len = q_len.astype(jnp.int32)
    brow, bidx = live_query_blocks(q_len, BQ, T)

    kernel = functools.partial(
        _mla_kernel, scale=scale, page_size=P, pages_per_chunk=C, bq=BQ,
        kb=KB,
    )
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=brow.shape,
        in_specs=[any_spec] * 3,
        out_specs=any_spec,
        scratch_shapes=[
            pltpu.VMEM((BQ, H, W), dt),                       # qbuf
            pltpu.VMEM((2, C, P, W), dt),                     # kvbuf
            pltpu.VMEM((KB, W), dt),                          # knbuf
            pltpu.VMEM((BQ, H, R), q.dtype),                  # obuf
            pltpu.SemaphoreType.DMA((2,)),                    # sems
            pltpu.SemaphoreType.DMA(()),                      # fsem
            pltpu.SemaphoreType.DMA(()),                      # qsem
            pltpu.SemaphoreType.DMA(()),                      # osem
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tpad, H, R), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        name="mla_ragged_paged_attention_tpu",
    )(
        brow, bidx,
        t0.astype(jnp.int32), q_len, hist.astype(jnp.int32),
        tables.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        qp, kn, kv_pages,
    )
    return out[:T]
