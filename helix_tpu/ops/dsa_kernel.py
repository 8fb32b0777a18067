"""Pallas TPU kernels of sparse latent attention behind an indexer
(``ops/dsa.py``): all over operands XLA has gathered from the pools, so none
walks a page table.

- ``dsa_index_scores_tpu``: ``I[r, t, s] = sum_j w[t, j] relu(q[t, j] .
  k[r, s])``.  A grid step is a block of ``BQ`` queries (their ``Hi`` heads
  the rows of ONE ``[BQ * Hi, Di]`` operand) against ``BS`` keys: one MXU
  product ``[BQ * Hi, BS]`` in float32, the ReLU and the heads' weights on
  the VPU, the sum over heads a sublane reduction; only ``[BQ, BS]`` is
  written, so ``[heads, queries, keys]`` is never in HBM.
- ``dsa_threshold_tpu``: a chunk's choice as two numbers a query
  (``ops/dsa.py::topk_mask``'s set, bit for bit).  A grid step copies a
  block of queries' scores over the row's LIVE key blocks and its fresh
  tokens into one VMEM scratch, turns them into ordered keys in place
  (masked by position: two scalars a row, never a ``[queries, keys]``
  ``valid``), and bisects over the keys' bits there: 32 passes of
  compare-and-count that never leave VMEM; where more keys reach the
  threshold than ``topk`` (a tie AT it: rare), a second bisection finds the
  position that cuts them.
- ``mla_sparse_attention_tpu``: absorbed-form latent attention of a block of
  ``BQ`` queries' ``H`` heads (rows of one ``[BQ * H, W]`` operand in the
  latent pool's row layout) over key blocks ``[BS, W]``: online softmax in
  float32, operands in the pool's dtype, the values the latent lanes of the
  same rows.  Two entry points of the one body (``_softmax_step``), under
  the one name a trace finds: a DECODE row's, one grid step over its
  ``topk`` gathered rows under a bias of its own; a CHUNK's
  (``mla_sparse_chunk_attention_tpu``), which walks the row's live history
  blocks and then the fresh rows and makes each step's ``[BQ, BS]`` mask
  from the index scores and ``dsa_threshold_tpu``'s two numbers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from helix_tpu.ops.attention import DEFAULT_MASK_VALUE
from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry

SCORE_QUERY_BLOCK = 32      # queries a scoring step (x Hi heads = MXU rows)
SCORE_KEY_BLOCK = 512
THRESHOLD_QUERY_BLOCK = 64  # queries whose live scores a threshold step holds
ATTN_QUERY_BLOCK = 32       # queries an attention step (x H heads)
ATTN_KEY_BLOCK = 512
DECODE_KEY_BLOCK = 2048     # a decode row's chosen keys in one step


def check_dsa_geometry(index_heads: int, index_dim: int, heads: int,
                       width: int):
    """Raise :class:`UnsupportedKernelGeometry` for what Mosaic refuses:
    index heads or query heads that do not fill float32 sublane tiles, an
    index key or a latent row that is not whole 128-lane tiles."""
    why = None
    if index_heads % 8 or heads % 8:
        why = (f"{index_heads} index heads / {heads} query heads do not "
               "fill whole sublane tiles")
    elif index_dim % 128 or width % 128:
        why = (f"an index key of {index_dim} / a latent row of {width} is "
               "not whole 128-lane tiles")
    if why:
        raise UnsupportedKernelGeometry(
            f"sparse latent attention kernels: no TPU lowering: {why}.  "
            "Serve this geometry with attn_backend='reference'")


def _precision(dtype):
    """bf16 operands go to the MXU as they are stored, whatever
    ``jax_default_matmul_precision`` says (Mosaic has no fp32-precision
    product of bf16 operands); float32 operands keep the caller's."""
    return jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


def _pad_to(x, axis: int, multiple: int):
    n = -x.shape[axis] % multiple
    if not n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n)
    return jnp.pad(x, pad)


def _live(lim_ref, r, j, bs: int):
    """Key block ``j`` of row ``r`` holds a key some query may see: it
    starts under the row's ``lim`` leading keys."""
    return j * bs < lim_ref[r]


def _key_block(lim_ref, r, j, bs: int):
    """The block a grid step fetches: its own where it is live; a dead one
    names the row's last live block, so that the pipeline fetches nothing
    new for it."""
    return jnp.minimum(j, jnp.maximum((lim_ref[r] + bs - 1) // bs - 1, 0))


def _scores_kernel(lim_ref, q_ref, w_ref, k_ref, o_ref, *, bq: int,
                   heads: int, bs: int):
    r, j = pl.program_id(0), pl.program_id(2)

    @pl.when(_live(lim_ref, r, j, bs))
    def _():
        q = q_ref[0]                               # [BQ * Hi, Di]
        k = k_ref[0]                               # [BS, Di]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=_precision(q.dtype),
            preferred_element_type=jnp.float32)    # [BQ * Hi, BS]
        s = jnp.maximum(s, 0.0) * w_ref[0]         # w: [BQ * Hi, 1]
        o_ref[0] = jnp.sum(s.reshape(bq, heads, s.shape[-1]), axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dsa_index_scores_tpu(q, w, keys, lim=None, *, interpret: bool = False):
    """``q [Rq, T, Hi, Di]``, ``w [Rq, T, Hi]``, ``keys [R, S, Di]`` ->
    ``[R, T, S]`` float32 (``ops/paged.py::dsa_index_scores_reference``).
    ``lim [R]``: only a row's first ``lim`` keys can be seen by a query; a
    key block past them is neither fetched nor scored and its scores are
    UNSPECIFIED (the caller masks by position)."""
    Rq, T, Hi, Di = q.shape
    R, S, _ = keys.shape
    lim = (jnp.full((R,), S, jnp.int32) if lim is None
           else lim.astype(jnp.int32))
    if not interpret:
        check_dsa_geometry(Hi, Di, 8, 128)
    dt = keys.dtype
    BQ = min(SCORE_QUERY_BLOCK, -(-T // 8) * 8) if T > 1 else 1
    BS = min(SCORE_KEY_BLOCK, -(-S // 128) * 128)
    qf = _pad_to(q.astype(dt), 1, BQ)
    Tp = qf.shape[1]
    qf = qf.reshape(Rq, Tp * Hi, Di)
    wf = _pad_to(w.astype(jnp.float32), 1, BQ).reshape(Rq, Tp * Hi, 1)
    kf = _pad_to(keys, 1, BS)
    Sp = kf.shape[1]
    own = (lambda r: r) if Rq == R else (lambda r: 0)
    nb = Sp // BS
    block = functools.partial(_key_block, bs=BS)
    out = pl.pallas_call(
        functools.partial(_scores_kernel, bq=BQ, heads=Hi, bs=BS),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, Tp // BQ, nb),
            in_specs=[
                pl.BlockSpec((1, BQ * Hi, Di),
                             lambda r, i, j, lim: (own(r), i, 0)),
                pl.BlockSpec((1, BQ * Hi, 1),
                             lambda r, i, j, lim: (own(r), i, 0)),
                pl.BlockSpec((1, BS, Di),
                             lambda r, i, j, lim: (r, block(lim, r, j), 0)),
            ],
            out_specs=pl.BlockSpec((1, BQ, BS),
                                   lambda r, i, j, lim: (r, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((R, Tp, Sp), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        name="dsa_index_scores_tpu",
    )(lim, qf, wf, kf)
    return out[:, :T, :S]


_MIN = -(1 << 31)           # the key of an entry that does not count
_MAX = (1 << 31) - 1


def _order(x):
    """float32 -> int32 whose SIGNED order is the floats' order
    (``ops/dsa.py::_ordered_bits`` with the top bit flipped: Mosaic compares
    signed), held over ``_MIN`` as ``topk_mask`` holds its keys over 0."""
    u = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.maximum(jnp.where(u < 0, u ^ jnp.int32(_MAX), u),
                       jnp.int32(_MIN + 1))


def _iota(shape, axis: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _row_blocks(t0, end, lim, q0, *, bq: int, bs: int):
    """The key blocks in which a query of the block ``[q0, q0 + bq)`` may
    keep a key of the row ``[t0, end)`` behind ``lim`` cached keys: ``(nh,
    f_lo, f_hi)``, the row's first ``nh`` history blocks and its fresh
    blocks ``[f_lo, f_hi)`` (from the row's start to the block's last
    query); none of either for a block of queries outside the row."""
    has = jnp.maximum(q0, t0) < jnp.minimum(q0 + bq, end)
    f_lo = t0 // bs
    return (jnp.where(has, (lim + bs - 1) // bs, 0), f_lo,
            jnp.where(has, (jnp.minimum(q0 + bq, end) + bs - 1) // bs, f_lo))


def _threshold_kernel(t0_ref, ql_ref, lim_ref, sh_hbm, sf_hbm, thr_ref,
                      tie_ref, buf, sem, *, bq: int, bs: int, nbh: int,
                      k: int, base: int, bits: int):
    r, i = pl.program_id(0), pl.program_id(1)
    t0, end, lim = t0_ref[r], t0_ref[r] + ql_ref[r], lim_ref[r]
    q0 = i * bq
    nh, f_lo, f_hi = _row_blocks(t0, end, lim, q0, bq=bq, bs=bs)
    # a query outside row r keeps nothing of it: all ones, over every key
    thr_ref[0] = jnp.full((bq, 1), -1, jnp.int32)
    tie_ref[0] = jnp.full((bq, 1), -1, jnp.int32)

    def walk(f, carry=0):
        """``f(scratch slot, block, whether of the history, carry)`` over
        the live history blocks, then the fresh ones."""
        carry = jax.lax.fori_loop(
            0, nh, lambda j, c: f(j, j, True, c), carry)
        return jax.lax.fori_loop(
            f_lo, f_hi, lambda j, c: f(nbh + j, j, False, c), carry)

    @pl.when(nh + f_hi - f_lo > 0)
    def _():
        rows = pl.ds(pl.multiple_of(q0, bq), bq)

        def copy(slot, j, hist):
            cols = pl.ds(pl.multiple_of(j * bs, bs), bs)
            return pltpu.make_async_copy(
                sh_hbm.at[r, rows, cols] if hist else sf_hbm.at[rows, cols],
                buf.at[slot], sem)

        walk(lambda slot, j, hist, c: copy(slot, j, hist).start() or c)
        walk(lambda slot, j, hist, c: copy(slot, j, hist).wait() or c)

        t = q0 + _iota((bq, 1), 0)
        mine = (t >= t0) & (t < end)
        lane = _iota((1, bs), 1)

        # scores -> keys in place (as float32 bit patterns), masked by
        # position: a history key counts under the row's ``lim``, a fresh
        # one from the row's start to the query itself
        def keys(slot, j, hist, c):
            s = j * bs + lane
            ok = mine & ((s < lim) if hist else (s >= t0) & (s <= t))
            key = jnp.where(ok, _order(buf[slot]), jnp.int32(_MIN))
            buf[slot] = jax.lax.bitcast_convert_type(key, jnp.float32)
            return c

        walk(keys)

        def count(pred):
            """``[bq, 1]``: a query's live keys ``pred(key block, position
            of the block's first key)`` holds of; a pass over VMEM."""
            def fold(slot, j, hist, acc):
                m = pred(jax.lax.bitcast_convert_type(buf[slot], jnp.int32),
                         j * bs + (0 if hist else base)).astype(jnp.int32)
                return acc + sum(m[:, c:c + 128] for c in range(0, bs, 128))

            return jnp.sum(walk(fold, jnp.zeros((bq, 128), jnp.int32)),
                           axis=-1, keepdims=True)

        # ``topk_mask``'s bisection: the largest value that ``k`` keys reach
        # (``v`` its unsigned bit pattern, ``n`` how many reach it)
        def bit(b, carry):
            v, n = carry
            cand = v | (jnp.int32(1) << (31 - b))
            got = count(lambda x, _: x >= (cand ^ jnp.int32(_MIN)))
            return (jnp.where(got >= k, cand, v), jnp.where(got >= k, got, n))

        zero = jnp.zeros((bq, 1), jnp.int32)
        v, n = jax.lax.fori_loop(0, 32, bit, (zero, zero))
        thr = jnp.where(v == 0, 1, v)           # 0: fewer than k are valid
        thr_ref[0] = jnp.where(mine, thr, -1)
        tie_ref[0] = jnp.where(mine, jnp.int32(_MAX), -1)
        over = mine & (n > k)

        # more than k reach it: of those AT it, the ``k - above`` of smallest
        # position stay; the position of the last, by bisection again
        @pl.when(jnp.max(over.astype(jnp.int32)) > 0)
        def _():
            ts = thr ^ jnp.int32(_MIN)
            need = k - count(lambda x, _: x > ts)

            def pbit(b, p):
                cand = p | (jnp.int32(1) << (bits - 1 - b))
                got = count(lambda x, p0: (x == ts) & (p0 + lane < cand))
                return jnp.where(got < need, cand, p)

            p = jax.lax.fori_loop(0, bits, pbit, zero)
            tie_ref[0] = jnp.where(over, p, tie_ref[0])


def _score_blocks(sc_h, sc_f, bq: int, bs: int):
    """The two score arrays in whole blocks ``[R, Tp, Sp]`` / ``[Tp, Tfp]``
    (at the cell's sizes as they are; a row axis with no history is one
    block no program reads)."""
    sh = _pad_to(_pad_to(sc_h, 1, bq), 2, bs)
    if not sc_h.shape[2]:
        sh = jnp.zeros(sh.shape[:2] + (bs,), jnp.float32)
    return sh, _pad_to(_pad_to(sc_f, 0, bq), 1, bs)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def dsa_threshold_tpu(sc_h, sc_f, t0, q_len, lim, *, topk: int,
                      interpret: bool = False):
    """What ``ops/dsa.py::topk_mask`` chooses for each query of a flat axis
    of ``T`` fresh tokens in rows ``[t0, t0 + q_len)``, as TWO numbers a
    query.  ``sc_h [R, T, S]`` float32: every query against each row's
    history, of which a query of row ``r`` counts the first ``lim[r]`` keys
    (the blocks of ``SCORE_KEY_BLOCK`` past them are never read: they may
    hold anything); ``sc_f [T, T]``: against the fresh tokens, of which it
    counts those of its row up to itself.  Returns ``thr [R, T]`` uint32,
    the ordered bits (``_ordered_bits``) of the query's ``topk``-th largest
    counted score (1 where it has no more than ``topk``), and ``tie [R, T]``
    int32: a key is kept iff it counts and its bits are over ``thr``, or AT
    it at a position (history ``s``; fresh ``S + f``) no larger than ``tie``.
    A query outside row ``r`` keeps nothing of it.  A grid step holds a
    query block's live scores in VMEM and makes the 32 passes there."""
    R, T, S = sc_h.shape
    BQ = min(THRESHOLD_QUERY_BLOCK, -(-T // 8) * 8)
    BS = min(SCORE_KEY_BLOCK, -(-max(S, T) // 128) * 128)
    sh, sf = _score_blocks(sc_h, sc_f, BQ, BS)
    Tp, nbh, nbf = sh.shape[1], sh.shape[2] // BS, sf.shape[1] // BS
    spec = pl.BlockSpec((1, BQ, 1), lambda r, i, *_: (r, i, 0))
    thr, tie = pl.pallas_call(
        functools.partial(_threshold_kernel, bq=BQ, bs=BS, nbh=nbh, k=topk,
                          base=S, bits=(S + nbf * BS).bit_length()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R, Tp // BQ),
            in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)] * 2,
            out_specs=[spec, spec],
            scratch_shapes=[
                pltpu.VMEM((nbh + nbf, BQ, BS), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((R, Tp, 1), jnp.int32)] * 2,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        name="dsa_threshold_tpu",
    )(t0.astype(jnp.int32), q_len.astype(jnp.int32), lim.astype(jnp.int32),
      sh, sf)
    return (jax.lax.bitcast_convert_type(thr[:, :T, 0], jnp.uint32),
            tie[:, :T, 0])


def _softmax_init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full(m_ref.shape, DEFAULT_MASK_VALUE, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)


def _softmax_step(q, kv, b, m_ref, l_ref, acc_ref, *, bq: int, heads: int,
                  latent: int):
    """One online-softmax step: ``q [BQ * H, W]`` against a key block ``kv
    [BS, W]`` under a per-query bias ``b [BQ, BS]``."""
    s = jax.lax.dot_general(
        q, kv, (((1,), (1,)), ((), ())), precision=_precision(q.dtype),
        preferred_element_type=jnp.float32)        # [BQ * H, BS]
    # a query's bias over its H heads' rows: aligned sublane slices
    s = jnp.concatenate(
        [s[t * heads:(t + 1) * heads] + b[t:t + 1] for t in range(bq)],
        axis=0) if bq > 1 else s + b
    keep = s > 0.5 * DEFAULT_MASK_VALUE
    s = jnp.where(keep, s, DEFAULT_MASK_VALUE)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p.astype(kv.dtype), kv[:, :latent], (((1,), (0,)), ((), ())),
        precision=_precision(kv.dtype), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _softmax_done(o_ref, l_ref, acc_ref):
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _softmax_scratch(rows: int, latent: int):
    return [pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, latent), jnp.float32)]


def _sparse_kernel(q_ref, kv_ref, b_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   bq: int, heads: int, latent: int):
    j = pl.program_id(2)
    pl.when(j == 0)(lambda: _softmax_init(m_ref, l_ref, acc_ref))
    _softmax_step(q_ref[0], kv_ref[0], b_ref[0], m_ref, l_ref, acc_ref,
                  bq=bq, heads=heads, latent=latent)
    pl.when(j == pl.num_programs(2) - 1)(
        lambda: _softmax_done(o_ref, l_ref, acc_ref))


@functools.partial(jax.jit, static_argnames=("latent", "interpret"))
def mla_sparse_attention_tpu(q, kv, bias, *, latent: int,
                             interpret: bool = False):
    """``q [Rq, T, H, W]``, ``kv [R, S, W]``, ``bias [R, T, S]`` -> ``[R, T,
    H, latent]`` (``ops/paged.py::mla_sparse_attention_reference``): a
    decode row over the rows it gathered."""
    Rq, T, H, W = q.shape
    R, S, _ = kv.shape
    if not interpret:
        check_dsa_geometry(8, 128, H, W)
    BQ = 1 if T == 1 else min(ATTN_QUERY_BLOCK, -(-T // 8) * 8)
    BS = min(DECODE_KEY_BLOCK if T == 1 else ATTN_KEY_BLOCK,
             -(-S // 128) * 128)
    qf = _pad_to(q.astype(kv.dtype), 1, BQ)
    Tp = qf.shape[1]
    qf = qf.reshape(Rq, Tp * H, W)
    kf = _pad_to(kv, 1, BS)
    Sp = kf.shape[1]
    bf = jnp.pad(bias.astype(jnp.float32),
                 ((0, 0), (0, Tp - T), (0, Sp - S)),
                 constant_values=DEFAULT_MASK_VALUE)
    own = (lambda r: r) if Rq == R else (lambda r: 0)
    out = pl.pallas_call(
        functools.partial(_sparse_kernel, bq=BQ, heads=H, latent=latent),
        grid=(R, Tp // BQ, Sp // BS),
        in_specs=[
            pl.BlockSpec((1, BQ * H, W), lambda r, i, j: (own(r), i, 0)),
            pl.BlockSpec((1, BS, W), lambda r, i, j: (r, j, 0)),
            pl.BlockSpec((1, BQ, BS), lambda r, i, j: (r, i, j)),
        ],
        out_specs=pl.BlockSpec((1, BQ * H, latent),
                               lambda r, i, j: (r, i, 0)),
        scratch_shapes=_softmax_scratch(BQ * H, latent),
        out_shape=jax.ShapeDtypeStruct((R, Tp * H, latent), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024,
        ),
        name="mla_sparse_attention_tpu",
    )(qf, kf, bf)
    return out.reshape(R, Tp, H, latent)[:, :T]


def _chunk_kernel(t0_ref, ql_ref, lim_ref, q_ref, thr_ref, tie_ref, kvh_hbm,
                  kvf_hbm, sh_hbm, sf_hbm, o_ref, kvbuf, scbuf, sems, m_ref,
                  l_ref, acc_ref, *, bq: int, heads: int, latent: int,
                  bs: int, base: int):
    r, i = pl.program_id(0), pl.program_id(1)
    t0, end, lim = t0_ref[r], t0_ref[r] + ql_ref[r], lim_ref[r]
    q0 = i * bq
    _softmax_init(m_ref, l_ref, acc_ref)
    # the key blocks in which some query of the block may keep a key of row
    # r: the row's live history, then its fresh tokens up to the block's
    # last query; none for a block of queries outside the row.  The walk is
    # a loop INSIDE the program (a grid over the table's width would spend a
    # third of a microsecond on every block it skips)
    nh, f_lo, f_hi = _row_blocks(t0, end, lim, q0, bq=bq, bs=bs)
    n = nh + f_hi - f_lo
    rows = pl.ds(pl.multiple_of(q0, bq), bq)

    def copies(c, slot):
        """Block ``c`` of the walk into buffer ``slot``: its latent rows and
        the query block's scores of them."""
        off = pl.multiple_of(jnp.where(c < nh, c, f_lo + c - nh) * bs, bs)
        return (
            (c < nh, kvh_hbm.at[r, pl.ds(off, bs)], kvbuf, 0),
            (c < nh, sh_hbm.at[r, rows, pl.ds(off, bs)], scbuf, 1),
            (c >= nh, kvf_hbm.at[pl.ds(off, bs)], kvbuf, 0),
            (c >= nh, sf_hbm.at[rows, pl.ds(off, bs)], scbuf, 1),
        )

    def start(c, slot):
        for when, src, buf, k in copies(c, slot):
            @pl.when(when)
            def _():
                pltpu.make_async_copy(src, buf.at[slot],
                                      sems.at[k, slot]).start()

    def wait(slot):
        for _, src, buf, k in copies(0, slot)[2:]:
            pltpu.make_async_copy(src, buf.at[slot], sems.at[k, slot]).wait()

    @pl.when(n > 0)
    def _():
        start(0, 0)

    t = q0 + _iota((bq, 1), 0)
    mine = (t >= t0) & (t < end)
    lane = _iota((1, bs), 1)
    ts, tie = thr_ref[0] ^ jnp.int32(_MIN), tie_ref[0]

    def block(c, carry):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n)
        def _():
            start(c + 1, 1 - slot)

        wait(slot)
        # the query's own mask, from the scores it chose by: a history key
        # counts under the row's ``lim``, a fresh one from the row's start
        # to the query itself
        s = jnp.where(c < nh, c, f_lo + c - nh) * bs + lane
        counts = mine & (s >= jnp.where(c < nh, 0, t0)) & (
            s <= jnp.where(c < nh, lim - 1, t))
        p = s + jnp.where(c < nh, 0, base)
        key = _order(scbuf[slot])
        keep = counts & ((key > ts) | ((key == ts) & (p <= tie)))
        _softmax_step(q_ref[...], kvbuf[slot],
                      jnp.where(keep, 0.0, DEFAULT_MASK_VALUE), m_ref, l_ref,
                      acc_ref, bq=bq, heads=heads, latent=latent)
        return carry

    jax.lax.fori_loop(0, n, block, 0)
    _softmax_done(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("latent", "interpret"))
def mla_sparse_chunk_attention_tpu(q, kv_h, kv_f, sc_h, sc_f, thr, tie, t0,
                                   q_len, lim, *, latent: int,
                                   interpret: bool = False):
    """The chunk form: a flat axis of ``T`` fresh tokens in rows ``[t0, t0 +
    q_len)``, each query over its row's history ``kv_h [R, S, W]`` (the
    first ``lim[r]`` rows; the key blocks past them are neither fetched nor
    multiplied) and the fresh rows ``kv_f [T, W]`` of its row up to itself,
    keeping what ``dsa_threshold_tpu``'s ``thr`` / ``tie [R, T]`` say of the
    scores ``sc_h [R, T, S]`` / ``sc_f [T, T]`` it is handed: a grid step
    makes its ``[BQ, BS]`` mask on the VPU.  ``q [T, H, W]`` -> ``[R, T, H,
    latent]``: a query outside row ``r`` gets zeros there."""
    T, H, W = q.shape
    R, S, _ = kv_h.shape
    if not interpret:
        check_dsa_geometry(8, 128, H, W)
    dt = kv_h.dtype
    BQ = min(ATTN_QUERY_BLOCK, -(-T // 8) * 8)
    BS = min(ATTN_KEY_BLOCK, -(-max(S, T) // 128) * 128)
    qf = _pad_to(q.astype(dt), 0, BQ)
    Tp = qf.shape[0]
    qf = qf.reshape(Tp * H, W)
    kh = _pad_to(kv_h, 1, BS) if S else jnp.zeros((R, BS, W), dt)
    kf = _pad_to(kv_f.astype(dt), 0, BS)
    sh, sf = _score_blocks(sc_h, sc_f, BQ, BS)
    per_query = [jnp.pad(jax.lax.bitcast_convert_type(x, jnp.int32),
                         ((0, 0), (0, Tp - T)), constant_values=-1)[..., None]
                 for x in (thr, tie)]
    number = pl.BlockSpec((1, BQ, 1), lambda r, i, *_: (r, i, 0))
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, bq=BQ, heads=H, latent=latent,
                          bs=BS, base=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R, Tp // BQ),
            in_specs=[
                pl.BlockSpec((BQ * H, W), lambda r, i, *_: (i, 0)),
                number, number,
                *[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)] * 4,
            ],
            out_specs=pl.BlockSpec((1, BQ * H, latent),
                                   lambda r, i, *_: (r, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, BS, W), dt),
                pltpu.VMEM((2, BQ, BS), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
                *_softmax_scratch(BQ * H, latent),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, Tp * H, latent), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024,
        ),
        name="mla_sparse_attention_tpu",
    )(t0.astype(jnp.int32), q_len.astype(jnp.int32), lim.astype(jnp.int32),
      qf, *per_query, kh, kf, sh, sf)
    return out.reshape(R, Tp, H, latent)[:, :T]
