"""Pallas TPU sliding-window attention over per-slot rings of K/V.

``ops/paged_kernel.py``'s ragged kernel with the page walk taken out: a row's
history is not a table of 16-token pages but ONE ring ``[W, KVH, D]`` a pool
(``ops/window.py``), contiguous in the state pool ``[L, slots, W, KVH, D]``.

- Row metadata (``t0`` / ``q_len`` / ``hist`` / ``slots``) and the layer index
  are scalar-prefetched; the grid walks the list of LIVE query blocks (a
  decode row is one one-token block, a chunk row blocks of
  ``chunk_query_block`` tokens: 128 at a query group of 8, what the bucket
  holds under that), as the ragged kernel's does.
- A row's ring comes HBM -> VMEM in ONE async DMA a pool (1 MB of K and 1 MB of
  V at 512 x 8 x 128 in bf16), into one of two slots: the ring of the NEXT
  block's row is started before this block computes (the grid is sequential,
  the scratch persists), and the blocks of one row share the ring their first
  block fetched: a 512-token chunk reads its ring once, not once a block.
- A row with no history fetches nothing.
- The mask is by POSITION: ring row ``j`` of a sequence with ``hist`` tokens
  behind it holds position ``hist - 1 - ((hist - 1 - j) mod W)``; negative:
  not this sequence's (a ring is never cleared); more than ``W - 1`` behind
  the query: out of its window.  Fresh tokens are attended raw under the
  causal and the window mask; persisting them is the caller's
  (``ops.window.write_ring``, after the call).
- A DECODE call (one-token blocks; q, the fresh rows and the output resident
  in VMEM) walks the ring's chunks a row has written, and scores all heads of
  a block in one dot of the block-diagonal query ``[H, KVH * D]`` against the
  chunk viewed flat ``[tokens, KVH * D]`` (the ragged kernel's layout) under
  an online softmax: it is bound by the ring's bytes, not by its products.
- A CHUNK call's block is long and scores ONE KV HEAD AT A TIME
  (``_chunk_block``): the row's first block lays the ring and the row's fresh
  keys out head-major and by position, once a row; every block then takes
  the ``W + BQ`` keys its queries can see as one window of that layout, a
  kv head's queries ``[BQ x group, D]`` against that head's keys ``[W + BQ,
  D]`` in one dense product and a plain softmax.  No block-diagonal query is
  built and no key outside the block's window is read.
- Products run in the INPUTS' dtype with float32 accumulation: bf16 products
  are exact in float32, and the probabilities meet V in V's dtype as in
  ``ops/attention.py``'s flash kernel.
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
from helix_tpu.ops.paged_kernel import (
    check_geometry, chunk_query_block, live_query_blocks, vmem_scratch,
)


# rows a step when a row's ring and fresh keys are laid out head-major
_RELAY = 128


def _window_kernel(
    # scalar prefetch
    brow_ref,    # SMEM [NB] int32 row of each live query block (-1 = none)
    bidx_ref,    # SMEM [NB] int32 block index within its row
    t0_ref,      # SMEM [R] int32 row starts on the flat token axis
    qlen_ref,    # SMEM [R] int32 fresh tokens per row (0 = unused)
    hist_ref,    # SMEM [R] int32 tokens behind each row
    slots_ref,   # SMEM [R] int32 each row's slot in the pool
    layer_ref,   # SMEM [1] int32 layer index
    # inputs / outputs / scratch, in this order:
    #   qf, knf, vnf, k_hbm, v_hbm | of | kbuf, vbuf, sems, cur
    #   [, qbuf, obuf, kst, vst, kall, vall, fsems, qsem, osem]
    # A one-token block (``bq`` 1: a decode call) finds qf/knf/vnf/of whole
    # in VMEM; a chunk call's block streams its own through the last group.
    *refs,
    scale: float,
    window: int,
    ring_chunk: int,
    kv_heads: int,
    group: int,
    bq: int,
    kb: int,
):
    W, CT, KVH, BQ, KB = window, ring_chunk, kv_heads, bq, kb
    resident = BQ == 1
    qf, knf, vnf, k_hbm, v_hbm = refs[:5]
    of, kbuf, vbuf, sems, cur_ref, *rest = refs[5:]
    if not resident:
        qbuf, obuf, kst, vst, kall, vall, fsems, qsem, osem = rest
    b = pl.program_id(0)
    NB = brow_ref.shape[0]
    r = brow_ref[b]
    lyr = layer_ref[0]

    def ring_dma(row, slot, go: bool):
        """Start (``go``) or wait for ``row``'s rings in ``slot``: the ring
        whole, and for a chunk call its first rows once more behind it, so
        that a run of them from any row on is contiguous."""
        at = slots_ref[row]
        for j, (pool, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
            again = buf.shape[1] - W
            cps = [pltpu.make_async_copy(
                pool.at[lyr, at],
                buf.at[slot, pl.ds(0, W)] if again else buf.at[slot],
                sems.at[slot, j])]
            if again:
                cps.append(pltpu.make_async_copy(
                    pool.at[lyr, at, pl.ds(0, again)],
                    buf.at[slot, pl.ds(W, again)], sems.at[slot, j]))
            for cp in cps:
                cp.start() if go else cp.wait()

    # The ring of a row's first block is in flight before its program starts:
    # the block before it (of another row) issued it, into the slot that
    # ``cur_ref`` hands on.  Block 0 has no one before it.
    @pl.when(b == 0)
    def _():
        cur_ref[0] = 0
        if not resident:
            # past a row's fresh keys a block's window finds zeros, for good
            end = W + kst.shape[0]
            for buf in (kall, vall):
                buf[:, pl.ds(end, buf.shape[1] - end)] = jnp.zeros(
                    (KVH, buf.shape[1] - end, buf.shape[2]), buf.dtype)

        @pl.when((r >= 0) & (hist_ref[jnp.maximum(r, 0)] > 0))
        def _():
            ring_dma(r, 0, True)

    @pl.when(r >= 0)
    def _program():
        i = bidx_ref[b]
        qlen_r = qlen_ref[r]
        hist_r = hist_ref[r]
        base = t0_ref[r] + i * BQ

        if not resident:
            qcp = pltpu.make_async_copy(qf.at[pl.ds(base, BQ)], qbuf, qsem)
            qcp.start()
            # the row's fresh keys and values, the whole bucket of them at
            # its first block: they land behind the ring's laying out
            fresh = [
                pltpu.make_async_copy(
                    src.at[pl.ds(t0_ref[r], dst.shape[0])], dst, fsems.at[n])
                for n, (src, dst) in enumerate(((knf, kst), (vnf, vst)))]

            @pl.when(i == 0)
            def _():
                for cp in fresh:
                    cp.start()

        slot = cur_ref[0]
        nxt = brow_ref[jnp.minimum(b + 1, NB - 1)]
        has_next = (b + 1 < NB) & (nxt >= 0)
        moves_on = has_next & (nxt != r)
        # the next block keeps this slot if it is of this row, else it gets
        # the other one, which the block before this one has done with
        cur_ref[0] = jnp.where(moves_on, 1 - slot, slot)

        @pl.when(moves_on & (hist_ref[jnp.maximum(nxt, 0)] > 0))
        def _():
            ring_dma(jnp.maximum(nxt, 0), 1 - slot, True)

        @pl.when((i == 0) & (hist_r > 0))
        def _():
            ring_dma(r, slot, False)

        if not resident:
            _chunk_block(
                i, qlen_r, hist_r, kbuf.at[slot], vbuf.at[slot], qbuf, obuf,
                kst, vst, kall, vall, scale=scale, window=W, kv_heads=KVH,
                group=group, bq=BQ, fresh=fresh, q_copy=qcp)
            ocp = pltpu.make_async_copy(obuf, of.at[pl.ds(base, BQ)], osem)
            ocp.start()
            ocp.wait()
            return

        q = qf[pl.ds(base, BQ)]
        dot_dtype = q.dtype
        # (sliced and reshaped in float32, whose (8, 128) tile a group of 8
        # fills; the dots run in the inputs' dtype)
        q = q.astype(jnp.float32)            # [BQ, KVH, group, D]
        D = q.shape[-1]
        H = KVH * group
        RQ = BQ * H                          # q_bd rows
        # one MXU mode for bf16 operands, whatever the process-wide matmul
        # precision (the flash kernel's note)
        prec = (jax.lax.Precision.DEFAULT if dot_dtype == jnp.bfloat16
                else None)

        # Block-diagonal q [BQ*H, KVH*D]: kv head k's query rows occupy the
        # column block of its kv head (``ops/paged_kernel.py``).
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
        q_bd = jnp.concatenate(q_bd_rows, axis=0).astype(dot_dtype)
        # [BQ*H, KVH*D]
        r_iota = jax.lax.broadcasted_iota(jnp.int32, (RQ, 1), 0)
        tok_of_row = jax.lax.rem(r_iota, BQ * group) // group  # [RQ, 1]
        q_off_row = i * BQ + tok_of_row                         # [RQ, 1]
        q_pos_row = hist_r + q_off_row

        def scores(keys):
            return jax.lax.dot_general(
                q_bd, keys.astype(q_bd.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            ) * scale

        def online(carry, s, vals):
            """One online-softmax step over a block of (masked) scores."""
            m_prev, l_prev, acc_prev = carry   # [RQ,1],[RQ,1],[RQ,KVH*D]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc_prev * alpha + jax.lax.dot_general(
                p.astype(vals.dtype), vals, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            )
            return m_new, l_new, acc_new

        # ---- the ring, CT rows a step, masked by position -------------
        def body(ci, carry):
            rows = pl.ds(pl.multiple_of(ci * CT, CT), CT)
            k_flat = kbuf[slot, rows].reshape(CT, KVH * D)
            v_flat = vbuf[slot, rows].reshape(CT, KVH * D)
            j = ci * CT + jax.lax.broadcasted_iota(jnp.int32, (1, CT), 1)
            back = hist_r - 1 - j
            # (hist - 1 - j) mod W for either sign
            held = hist_r - 1 - jax.lax.rem(jax.lax.rem(back, W) + W, W)
            ok = (held >= 0) & (q_pos_row - held < W)   # [RQ, CT]
            # a fully masked row's exp(0) would weigh these values by 1: it
            # is divided out only while they are finite, and a pool is
            # zeros or a sequence's finite values
            s = jnp.where(ok, scores(k_flat), DEFAULT_MASK_VALUE)
            return online(carry, s, v_flat)

        carry = (
            jnp.full((RQ, 1), -jnp.inf, jnp.float32),
            jnp.zeros((RQ, 1), jnp.float32),
            jnp.zeros((RQ, KVH * D), jnp.float32),
        )
        nchunks = jnp.minimum(jax.lax.div(hist_r + CT - 1, CT), W // CT)
        carry = jax.lax.fori_loop(0, nchunks, body, carry)

        # ---- fresh tokens of this row, KB keys a step -----------------
        def fresh_body(j, carry):
            src = pl.ds(t0_ref[r] + j * KB, KB)
            kf = knf[src].reshape(KB, KVH * D)
            vf = vnf[src].reshape(KB, KVH * D)
            kv_off = j * KB + jax.lax.broadcasted_iota(
                jnp.int32, (1, KB), 1
            )                                   # [1, KB]
            # a partial tail block reads the NEXT row's fresh tokens (or
            # flat padding), which may be NaN: zero V out-of-row (the
            # ragged kernel's guard)
            vf = jnp.where(
                j * KB + jax.lax.broadcasted_iota(
                    jnp.int32, (KB, 1), 0
                ) < qlen_r,
                vf, 0,
            )
            ok = (kv_off < qlen_r) & (kv_off <= q_off_row) & (
                q_off_row - kv_off < W)                     # [RQ, KB]
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
            of[pl.ds(base, BQ), k] = out[
                k * BQ * group:(k + 1) * BQ * group,
                k * D:(k + 1) * D,
            ].reshape(BQ, group, D).astype(of.dtype)


def _chunk_block(
    i, qlen_r, hist_r,
    kring, vring,  # VMEM [W + again, KVH, D] the row's rings as the pool holds
                   # them, their first rows once more behind them
    qbuf,          # VMEM [BQ, KVH, G, D] the block's queries (in flight)
    obuf,          # VMEM [BQ, KVH, G, D] the block's output
    kst, vst,      # VMEM [F, KVH, D] the row's fresh K/V as they lie in HBM
    kall, vall,    # VMEM [KVH, W + F + pad, D] the row's keys by POSITION
    *, scale, window, kv_heads, group, bq, fresh, q_copy,
):
    """One long query block of a chunk row: each kv head's queries ``[BQ x
    group, D]`` meet that head's keys alone, a head at a time, in ONE product
    over the keys the block can see.

    The row's FIRST block lays its keys out head-major and BY POSITION
    (``[tokens, KVH, D] -> [KVH, tokens, D]``: the reshape every 8-token
    block used to make of every chunk, once a row): ``kall[k, m]`` is the key
    at position ``hist - W + m``, the ring's ``W`` rows from row ``hist mod
    W`` on (the oldest first; what the sequence has not written lands on
    negative positions) and the fresh keys behind them.  Query ``o`` of the
    row sees ``o < m <= o + W``, so a block's queries see the ``W + BQ`` keys
    from ``m = i BQ`` on: one window of static width at an offset, whatever
    the ring holds.  Its scores are one product, the softmax is plain (no
    running maximum to carry), and nothing outside the window is read."""
    W, KVH, G, BQ = window, kv_heads, group, bq
    D = qbuf.shape[-1]
    F = kst.shape[0]
    RQ = BQ * G
    CW = kall.shape[1] - F          # the window's width: W + BQ and a pad
    dot_dtype = qbuf.dtype
    prec = (jax.lax.Precision.DEFAULT if dot_dtype == jnp.bfloat16
            else None)

    def lay_out(src, row0, dst, at0, rows: int, keep=None):
        """``src[row0 : row0 + rows]`` ``[rows, KVH, D]`` to ``dst[:, at0 :
        at0 + rows]``; of the rows at and past ``keep`` zeros."""
        flat = src[pl.ds(row0, rows)].reshape(rows, KVH * D)
        if keep is not None:
            flat = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < keep,
                flat, 0)
        for k in range(KVH):
            dst[k, pl.ds(at0, rows)] = flat[:, k * D:(k + 1) * D]

    @pl.when(i == 0)
    def _():
        RL = kring.shape[0] - W     # (the rows that lie behind it again)

        @pl.when(hist_r > 0)
        def _():
            first = jax.lax.rem(hist_r, W)
            for n in range(W // RL):
                row0 = jax.lax.rem(first + n * RL, W)
                lay_out(kring, row0, kall, n * RL, RL)
                lay_out(vring, row0, vall, n * RL, RL)

        # no ring was fetched: whatever lies there is masked by position,
        # and weighs 0 only while it is finite
        @pl.when(hist_r == 0)
        def _():
            vall[:, pl.ds(0, W)] = jnp.zeros((KVH, W, D), vall.dtype)

        for cp in fresh:
            cp.wait()
        FL = min(F, _RELAY)
        for n in range(F // FL):
            lay_out(kst, n * FL, kall, W + n * FL, FL)
            # past the row lie the NEXT row's fresh tokens (or flat padding),
            # which may be NaN: zero V out-of-row (the ragged kernel's guard)
            lay_out(vst, n * FL, vall, W + n * FL, FL, keep=qlen_r - n * FL)

    # key m of the block's window (``m0 + c``) against query ``o`` (``m0 +
    # u``): inside the window by ``0 < c - u <= W``, a key at all by ``W -
    # hist <= m < W + q_len``
    m0 = pl.multiple_of(i * BQ, 8)
    c = jax.lax.broadcasted_iota(jnp.int32, (1, CW), 1)
    u = jax.lax.broadcasted_iota(jnp.int32, (RQ, 1), 0) // G
    is_key = (m0 + c >= W - hist_r) & (m0 + c < W + qlen_r)      # [1, CW]
    q_copy.wait()

    def head(k, _):
        # (reshaped in float32, whose (8, 128) tile a group of 8 fills; the
        # dots run in the inputs' dtype)
        q = qbuf[:, k].astype(jnp.float32).reshape(RQ, D).astype(dot_dtype)
        s = jax.lax.dot_general(
            q, kall[k, pl.ds(m0, CW)], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        ) * scale                                           # [RQ, CW]
        ok = is_key & (c - u > 0) & (c - u <= W)
        # a fully masked row's exp(0) weighs the values by 1: it is divided
        # out only while they are finite, and a pool is zeros or a
        # sequence's finite values
        s = jnp.where(ok, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        l = jnp.sum(p, axis=-1, keepdims=True)
        v = vall[k, pl.ds(m0, CW)]
        acc = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
        # (l >= 1, the row's maximum counts 1: a row with no key at all,
        # block-tail padding past the row, stays finite)
        out = acc / l                                       # [RQ, D]
        obuf[:, k] = out.reshape(BQ, G, D).astype(obuf.dtype)
        return 0

    jax.lax.fori_loop(0, KVH, head, 0)


@functools.partial(
    jax.jit, static_argnames=("scale", "max_q_len", "interpret"))
def window_attention_tpu(
    q,            # [T, H, D] flat fresh queries
    k_new,        # [T, KVH, D] fresh K/V, attended raw
    v_new,
    k_ring,       # [L, slots, W, KVH, D] — FULL pool (read-only here)
    v_ring,
    layer,        # scalar int32
    t0,           # [R] int32 row starts (ascending, disjoint)
    q_len,        # [R] int32 fresh tokens per row (0 = unused)
    hist,         # [R] int32 tokens behind each row
    slots,        # [R] int32 each row's slot in the pool
    *,
    scale: Optional[float] = None,
    max_q_len: Optional[int] = None,
    interpret: bool = False,
):
    """Returns ``out [T, H, D]``: ``ops.window.window_attention``'s contract
    (the window is the ring's length).  Rows may start at any offset; the
    flat axis is padded internally.  ``max_q_len``, a static bound on any
    row's fresh tokens (default: T), picks the query block: 1 token for a
    decode call, else ``chunk_query_block``'s long one."""
    T, H, D = q.shape
    L, nslots, W, KVH, _ = k_ring.shape
    if not interpret:
        check_geometry(H, KVH, D, k_ring.dtype.itemsize)
    group = H // KVH
    MQ = T if max_q_len is None else min(max_q_len, T)
    G = -(-group // 8) * 8
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if MQ == 1:
        # a decode call: ring rows a step of the walk (the ring is in VMEM
        # whole, the step bounds the scores' width), fresh keys a step
        BQ, KB, F, again = 1, 8, 8, 0
        CT = 256
        if W <= CT or W % CT:
            CT = W
    else:
        BQ, CT, KB = chunk_query_block(MQ, group), 0, 0   # (decode's steps)
        # the fresh keys a row's first block brings in: the bucket's
        F = -(-MQ // _RELAY) * _RELAY if MQ > _RELAY else -(-MQ // 8) * 8
        # the keys a block's queries can see, to the lanes' tile
        CW = -(-(W + BQ) // 128) * 128
        # ring rows a step of the laying out, which lie behind the ring again
        again = _RELAY if W % _RELAY == 0 else W
    # a row's last block, and its fresh keys' copy, run on past its end onto
    # the next rows' tokens (which their own blocks then write) or this pad
    Tpad = -(-(T + F + BQ) // 8) * 8
    tail = ((0, Tpad - T), (0, 0), (0, 0))
    k_new, v_new = jnp.pad(k_new, tail), jnp.pad(v_new, tail)
    qg = jnp.pad(
        q.reshape(T, KVH, group, D), (*tail[:2], (0, G - group), (0, 0)))

    q_len = q_len.astype(jnp.int32)
    brow, bidx = live_query_blocks(q_len, BQ, T)

    kernel = functools.partial(
        _window_kernel,
        scale=scale,
        window=W,
        ring_chunk=CT,
        kv_heads=KVH,
        group=G,
        bq=BQ,
        kb=KB,
    )
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)

    def whole(shape):
        return pl.BlockSpec(shape, lambda b, *_: (0,) * len(shape))

    held = []
    vmem = functools.partial(vmem_scratch, held)

    scratch = [
        vmem((2, W + again, KVH, D), k_ring.dtype),         # kbuf
        vmem((2, W + again, KVH, D), v_ring.dtype),         # vbuf
        pltpu.SemaphoreType.DMA((2, 2)),                    # sems
        pltpu.SMEM((1,), jnp.int32),                        # cur
    ]
    if BQ == 1:
        q_spec = out_spec = whole((Tpad, KVH, G, D))
        new_spec = whole((Tpad, KVH, D))
        held.append(8 * Tpad * KVH * 16 * D * q.dtype.itemsize)
    else:
        q_spec = out_spec = new_spec = any_spec
        scratch += [
            vmem((BQ, KVH, G, D), q.dtype),                 # qbuf
            vmem((BQ, KVH, G, D), q.dtype),                 # obuf
            vmem((F, KVH, D), k_new.dtype),                 # kst
            vmem((F, KVH, D), v_new.dtype),                 # vst
            vmem((KVH, F + CW, D), k_ring.dtype),           # kall
            vmem((KVH, F + CW, D), v_ring.dtype),           # vall
            pltpu.SemaphoreType.DMA((2,)),                  # fsems
            pltpu.SemaphoreType.DMA(()),                    # qsem
            pltpu.SemaphoreType.DMA(()),                    # osem
        ]
        # one head's q and output, its scores, mask and probabilities
        held.append(BQ * G * (4 * CW + 4 * D) * 4)
    vmem_limit = min(max(16 << 20, sum(held) + (8 << 20)), 100 << 20)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=brow.shape,
        in_specs=[q_spec, new_spec, new_spec, any_spec, any_spec],
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
        jnp.clip(slots.astype(jnp.int32), 0, nslots - 1),
        jnp.asarray(layer, jnp.int32).reshape(1),
        qg, k_new, v_new, k_ring, v_ring,
    )
    return out[:T, :, :group].reshape(T, H, D)
