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
- Grid is ``(R, NQ)``: program ``(r, i)`` owns 8-token query block ``i``
  of row ``r`` (programs past the row's ragged length skip everything) and
  computes all ``KVH`` head groups from the same VMEM-resident chunks.
  Rows are ragged: a decode row is 1 token, a verify row ``1+k`` tokens, a
  prefill row a whole chunk — the grid walks ONLY the pages and fresh
  blocks each row actually uses, which is where the padding-waste win
  comes from.
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
  ``[R, KVH, tokens]`` slab and the kernel streams one 128-lane window of
  it per chunk.  Dequantization is on the score side — K's scale
  multiplies the scores, V's the probabilities — so the MXU still sees
  fp32 operands and no scale ever crosses from lanes to sublanes.  Fresh
  tokens are attended at full precision; the write path quantizes
  through the shared codec.
- Scores for ALL heads of a q block come from ONE 128-aligned MXU dot:
  the block-diagonal q layout ``[8*H, KVH*D]`` (query head h occupies the
  column block of its kv head) against the chunk buffer viewed flat
  ``[T, KVH*D]`` — no per-head strided slices (the same trick the
  decode-only predecessor kernel used, extended to 8-token q blocks).

Layout contract: rows are disjoint and ascending on the flat axis; rows
may start at ANY offset.  A row's final partial query block writes
garbage into the following flat positions, but the grid iterates rows in
ascending order ("arbitrary" = sequential on TPU), so every later row's
program overwrites its own positions afterwards — and the wrapper pads
the flat axis with 8 tail tokens so the LAST row's spill lands in
scratch, never out of bounds.
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

BQ = 8  # query-block tokens: one f32 sublane tile; bounds ragged waste


class UnsupportedKernelGeometry(ValueError):
    """The ragged kernel has no TPU lowering for this head geometry."""


def check_geometry(num_heads: int, num_kv_heads: int, head_dim: int,
                   kv_itemsize: int = 2):
    """Raise :class:`UnsupportedKernelGeometry` for a geometry Mosaic
    refuses (per device: under a ``tp`` mesh pass the per-shard counts).

    - The kernel views a chunk as ``[tokens, KVH*D]`` with each head a
      whole number of 128-lane tiles; a head width such as Phi-3-mini's
      96 needs a relayout the compiler does not have ("unsupported shape
      cast").
    - A page is DMA'd as ``[P, KVH, D]``, and the pool's ``(KVH, D)``
      minor pair is tiled in 32-bit sublane packs: ``KVH`` kv heads of
      ``kv_itemsize`` bytes must fill one (2 heads in bf16, 4 in int8), or
      the pool is padded in HBM and the slice is refused ("must be aligned
      to tiling").  Qwen2-7B at tp=4 (one kv head per chip) is such a
      case.
    """
    why = None
    if head_dim % 128:
        why = "the head width must be a multiple of the 128 lanes"
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
    t0_ref,      # SMEM [R] int32 row starts on the flat token axis
    qlen_ref,    # SMEM [R] int32 fresh tokens per row (0 = unused)
    hist_ref,    # SMEM [R] int32 pages-resident history tokens per row
    pt_ref,      # SMEM [R, maxP] int32 page tables
    layer_ref,   # SMEM [1] int32 layer index
    # inputs / outputs / scratch — order depends on ``quantized``:
    #   plain: qf, knf, vnf, k_hbm, v_hbm | o_hbm
    #          | qbuf, kbuf, vbuf, knbuf, vnbuf, obuf, sems, fsems, qsem,
    #            osem
    #   quant: ... + ks_hbm, vs_hbm row slabs and ksbuf/vsbuf/ssems scratch
    *refs,
    scale: float,
    page_size: int,
    pages_per_chunk: int,
    max_pages: int,
    kv_heads: int,
    group: int,
    quantized: bool,
):
    if quantized:
        (qf, knf, vnf, k_hbm, v_hbm, ks_hbm, vs_hbm,
         o_hbm,
         qbuf, kbuf, vbuf, ksbuf, vsbuf, knbuf, vnbuf, obuf,
         sems, ssems, fsems, qsem, osem) = refs
    else:
        (qf, knf, vnf, k_hbm, v_hbm,
         o_hbm,
         qbuf, kbuf, vbuf, knbuf, vnbuf, obuf,
         sems, fsems, qsem, osem) = refs
    r = pl.program_id(0)
    i = pl.program_id(1)
    lyr = layer_ref[0]
    P, C, KVH = page_size, pages_per_chunk, kv_heads
    qlen_r = qlen_ref[r]
    hist_r = hist_ref[r]
    base = t0_ref[r] + i * BQ

    @pl.when(i * BQ < qlen_r)
    def _program():
        # ---- fetch this q block --------------------------------------
        qcp = pltpu.make_async_copy(
            qf.at[pl.ds(base, BQ)], qbuf, qsem
        )
        qcp.start()

        npages = jax.lax.div(hist_r + P - 1, P)
        nchunks = jax.lax.div(npages + C - 1, C)
        max_chunks = (max_pages + C - 1) // C

        def scale_copies(ci, slot):
            # one [KVH, C*P] lane-aligned window of the row's gathered
            # scale slab per chunk (see the wrapper)
            return [
                pltpu.make_async_copy(
                    src.at[r, :, pl.ds(ci * (C * P), C * P)],
                    dst.at[slot],
                    ssems.at[slot, j],
                )
                for j, (src, dst) in enumerate(
                    ((ks_hbm, ksbuf), (vs_hbm, vsbuf))
                )
            ]

        def start_chunk(ci, slot):
            if quantized:
                for cp in scale_copies(ci, slot):
                    cp.start()
            for c in range(C):  # static unroll over pages in a chunk
                @pl.when(ci * C + c < npages)
                def _():
                    page = pt_ref[r, ci * C + c]
                    pltpu.make_async_copy(
                        k_hbm.at[lyr, page],
                        kbuf.at[slot, c],
                        sems.at[slot, c, 0],
                    ).start()
                    pltpu.make_async_copy(
                        v_hbm.at[lyr, page],
                        vbuf.at[slot, c],
                        sems.at[slot, c, 1],
                    ).start()

        def wait_chunk(ci, slot):
            if quantized:
                for cp in scale_copies(ci, slot):
                    cp.wait()
            for c in range(C):
                @pl.when(ci * C + c < npages)
                def _():
                    page = pt_ref[r, ci * C + c]
                    pltpu.make_async_copy(
                        k_hbm.at[lyr, page],
                        kbuf.at[slot, c],
                        sems.at[slot, c, 0],
                    ).wait()
                    pltpu.make_async_copy(
                        v_hbm.at[lyr, page],
                        vbuf.at[slot, c],
                        sems.at[slot, c, 1],
                    ).wait()

        @pl.when(nchunks > 0)
        def _():
            start_chunk(0, 0)

        qcp.wait()
        q = qbuf[...].astype(jnp.float32)    # [BQ, KVH, group, D]
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
            m_prev, l_prev, acc_prev = carry   # [RQ,1],[RQ,1],[RQ,KVH*D]
            slot = jax.lax.rem(ci, 2)

            @pl.when(ci + 1 < nchunks)
            def _():
                start_chunk(ci + 1, jax.lax.rem(ci + 1, 2))

            wait_chunk(ci, slot)
            k_flat = kbuf[slot].reshape(C * P, KVH * D).astype(jnp.float32)
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
            s = jax.lax.dot_general(
                q_bd, k_flat, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                           # [RQ, T]
            if quantized:
                # dequantize on the score side: q_bd row block k only
                # ever meets kv head k, so K's per-(token, head) scale
                # multiplies the scores and V's the probabilities —
                # [RQ, T] lane-dense products instead of a lane->sublane
                # relayout of the scales against [T, KVH*D]
                s = s * head_rows(ksbuf[slot])
            s = jnp.where(in_range, s, DEFAULT_MASK_VALUE)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                p = p * head_rows(vsbuf[slot])
            acc_new = acc_prev * alpha + jax.lax.dot_general(
                p, v_flat, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc_new

        m0 = jnp.full((RQ, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((RQ, 1), jnp.float32)
        acc0 = jnp.zeros((RQ, KVH * D), jnp.float32)

        def guarded_body(ci, carry):
            return jax.lax.cond(
                ci < nchunks, lambda c: body(ci, c), lambda c: c, carry
            )

        m, l, acc = jax.lax.fori_loop(
            0, max_chunks, guarded_body, (m0, l0, acc0)
        )

        # ---- fresh tokens of this row, block by block (causal) --------
        def fresh_body(j, carry):
            m_prev, l_prev, acc_prev = carry
            src = t0_ref[r] + j * BQ
            kcp = pltpu.make_async_copy(
                knf.at[pl.ds(src, BQ)], knbuf, fsems.at[0]
            )
            vcp = pltpu.make_async_copy(
                vnf.at[pl.ds(src, BQ)], vnbuf, fsems.at[1]
            )
            kcp.start()
            vcp.start()
            kcp.wait()
            vcp.wait()
            kf = knbuf[...].reshape(BQ, KVH * D).astype(jnp.float32)
            vf = vnbuf[...].reshape(BQ, KVH * D).astype(jnp.float32)
            kv_off = j * BQ + jax.lax.broadcasted_iota(
                jnp.int32, (1, BQ), 1
            )                                   # [1, BQ]
            # a partial tail block reads the NEXT row's fresh tokens (or
            # flat padding); their softmax weight is exactly 0, but a
            # skipped neighbour row's uninitialized output feeds later
            # layers' projections, so its V here can be NaN — and
            # 0 * NaN still poisons the PV accumulation.  Zero V
            # out-of-row, same guard as the history path.
            vf = jnp.where(
                j * BQ + jax.lax.broadcasted_iota(
                    jnp.int32, (BQ, 1), 0
                ) < qlen_r,
                vf, 0,
            )
            ok = (kv_off < qlen_r) & (kv_off <= q_off_row)  # [RQ, BQ]
            s = jax.lax.dot_general(
                q_bd, kf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                           # [RQ, BQ]
            s = jnp.where(ok, s, DEFAULT_MASK_VALUE)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc_prev * alpha + jax.lax.dot_general(
                p, vf, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc_new

        m, l, acc = jax.lax.fori_loop(0, i + 1, fresh_body, (m, l, acc))

        # fully-masked q rows (block-tail padding past the row's ragged
        # length) have l == 0; guard the divide so garbage stays finite
        out = acc / jnp.where(l > 0, l, 1.0)    # [RQ, KVH*D]
        for k in range(KVH):                    # extract each head block
            obuf[:, k] = out[
                k * BQ * group:(k + 1) * BQ * group,
                k * D:(k + 1) * D,
            ].reshape(BQ, group, D).astype(obuf.dtype)
        ocp = pltpu.make_async_copy(
            obuf, o_hbm.at[pl.ds(base, BQ)], osem
        )
        ocp.start()
        ocp.wait()


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
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
    interpret: bool = False,
    k_scale=None,  # [L, N, KVH*P] f32 — present iff the pool is int8
    v_scale=None,
    **tiered,      # span_lo/span_hi/cold_* — NOT supported in-kernel yet
):
    """Returns ``out [T, H, D]``.  Rows may start at any offset; the
    flat axis is padded internally so partial query blocks never DMA out
    of bounds.

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
    # Mosaic tiles the (group, D) minor pair of the q/o blocks: a group
    # that is neither a whole sublane tile nor a power-of-two fraction of
    # one (Qwen2-7B: 28/4 = 7) is refused, so pad it with zero query heads
    # and slice them off the output
    G = group if group in (1, 2, 4) else -(-group // 8) * 8
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    C = max(1, 128 // P)
    C = min(C, maxP)
    quantized = k_scale is not None
    # pad so the last row's final (possibly unaligned, possibly partial)
    # 8-token query block stays in bounds: need Tpad >= T + (BQ - 1) and
    # Tpad % BQ == 0
    Tpad = (T + 2 * BQ - 2) // BQ * BQ
    if Tpad != T:
        zpad = Tpad - T
        q = jnp.concatenate([q, jnp.zeros((zpad, H, D), q.dtype)], axis=0)
        k_new = jnp.concatenate(
            [k_new, jnp.zeros((zpad, KVH, D), k_new.dtype)], axis=0
        )
        v_new = jnp.concatenate(
            [v_new, jnp.zeros((zpad, KVH, D), v_new.dtype)], axis=0
        )
    NQ = Tpad // BQ

    qg = q.reshape(Tpad, KVH, group, D)
    if G != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, G - group), (0, 0)))
    kernel = functools.partial(
        _ragged_kernel,
        scale=scale,
        page_size=P,
        pages_per_chunk=C,
        max_pages=maxP,
        kv_heads=KVH,
        group=G,
        quantized=quantized,
    )
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    in_specs = [any_spec] * (7 if quantized else 5)
    out_spec = any_spec
    scratch = [
        pltpu.VMEM((BQ, KVH, G, D), q.dtype),               # qbuf
        pltpu.VMEM((2, C, P, KVH, D), k_pages.dtype),       # kbuf
        pltpu.VMEM((2, C, P, KVH, D), v_pages.dtype),       # vbuf
    ]
    if quantized:
        scratch += [
            pltpu.VMEM((2, KVH, C * P), jnp.float32),       # ksbuf
            pltpu.VMEM((2, KVH, C * P), jnp.float32),       # vsbuf
        ]
    scratch += [
        pltpu.VMEM((BQ, KVH, D), k_new.dtype),              # knbuf
        pltpu.VMEM((BQ, KVH, D), v_new.dtype),              # vnbuf
        pltpu.VMEM((BQ, KVH, G, D), q.dtype),               # obuf
        pltpu.SemaphoreType.DMA((2, C, 2)),                 # sems
    ]
    if quantized:
        scratch += [pltpu.SemaphoreType.DMA((2, 2))]        # ssems
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),                      # fsems
        pltpu.SemaphoreType.DMA(()),                        # qsem
        pltpu.SemaphoreType.DMA(()),                        # osem
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
            tail = -maxP % C * P
            return jnp.pad(rows, ((0, 0), (0, 0), (0, tail)))

        inputs += (row_scales(k_scale), row_scales(v_scale))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(R, NQ),
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
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(
        t0.astype(jnp.int32),
        q_len.astype(jnp.int32),
        hist.astype(jnp.int32),
        tables.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        *inputs,
    )
    return out[:T, :, :group].reshape(T, H, D)
