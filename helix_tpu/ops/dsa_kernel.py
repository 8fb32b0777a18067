"""Pallas TPU kernels of sparse latent attention behind an indexer
(``ops/dsa.py``): both dense, over operands XLA has gathered from the pools,
so neither walks a page table and the pipeline's own double-buffered DMAs
feed them.

- ``dsa_index_scores_tpu``: ``I[r, t, s] = sum_j w[t, j] relu(q[t, j] .
  k[r, s])``.  A grid step is a block of ``BQ`` queries (their ``Hi`` heads
  the rows of ONE ``[BQ * Hi, Di]`` operand) against ``BS`` keys: one MXU
  product ``[BQ * Hi, BS]`` in float32, the ReLU and the heads' weights on
  the VPU, the sum over heads a sublane reduction; only ``[BQ, BS]`` is
  written, so ``[heads, queries, keys]`` is never in HBM.
- ``mla_sparse_attention_tpu``: absorbed-form latent attention of a block of
  ``BQ`` queries' ``H`` heads (rows of one ``[BQ * H, W]`` operand in the
  latent pool's row layout) over key blocks ``[BS, W]`` under a per-query
  bias ``[BQ, BS]`` (0 keeps a key, a large negative drops it): online
  softmax in float32, operands in the pool's dtype, the values the latent
  lanes of the same rows.  A decode row is one grid step over its ``topk``
  gathered rows; a chunk walks its dense history in key blocks.

Queries come with a leading row axis of ``R`` (a row's own: decode) or 1
(every row sees the same flat axis: a chunk), keys and bias with ``R``.
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
ATTN_QUERY_BLOCK = 16       # queries an attention step (x H heads)
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


def _live(lim_ref, r, j, bs: int, lead: int):
    """Key block ``j`` of row ``r`` holds a key some query may see: it
    starts under the row's ``lim`` leading keys, or behind the ``lead``
    leading positions (the fresh tokens' blocks, always live)."""
    return (j * bs < lim_ref[r]) | (j * bs >= lead)


def _key_block(lim_ref, r, j, bs: int, lead: int, blocks: int):
    """The block a grid step fetches: its own where it is live; a dead one
    names a live neighbour (the first block behind ``lead``, or the row's
    last live one), so that the pipeline fetches nothing new for it."""
    if lead < blocks * bs:
        dead = lead // bs
    else:
        dead = jnp.maximum((lim_ref[r] + bs - 1) // bs - 1, 0)
    return jnp.where(_live(lim_ref, r, j, bs, lead), j, dead)


def _scores_kernel(lim_ref, q_ref, w_ref, k_ref, o_ref, *, bq: int,
                   heads: int, bs: int, lead: int):
    r, j = pl.program_id(0), pl.program_id(2)

    @pl.when(_live(lim_ref, r, j, bs, lead))
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
    block = functools.partial(_key_block, bs=BS, lead=Sp, blocks=nb)
    out = pl.pallas_call(
        functools.partial(_scores_kernel, bq=BQ, heads=Hi, bs=BS, lead=Sp),
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


def _sparse_kernel(lim_ref, q_ref, kv_ref, b_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, bq: int, heads: int, latent: int, bs: int,
                   lead: int):
    r, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, DEFAULT_MASK_VALUE, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(_live(lim_ref, r, j, bs, lead))
    def _():
        q = q_ref[0]                                   # [BQ * H, W]
        kv = kv_ref[0]                                 # [BS, W]
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())), precision=_precision(q.dtype),
            preferred_element_type=jnp.float32)        # [BQ * H, BS]
        b = b_ref[0]                                   # [BQ, BS]
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

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("latent", "lead", "interpret"))
def mla_sparse_attention_tpu(q, kv, bias, lim=None, *, latent: int,
                             lead: int = 0, interpret: bool = False):
    """``q [Rq, T, H, W]``, ``kv [R, S, W]``, ``bias [R, T, S]`` -> ``[R, T,
    H, latent]`` (``ops/paged.py::mla_sparse_attention_reference``).  ``lim
    [R]`` with ``lead``: of a row's first ``lead`` keys (its gathered
    history; a multiple of the key block) only the first ``lim`` can be kept
    by a query; the key blocks between are neither fetched nor multiplied
    (the bias drops their keys anyway).  The keys behind ``lead`` (the fresh
    tokens) are always walked."""
    Rq, T, H, W = q.shape
    R, S, _ = kv.shape
    lim = (jnp.full((R,), S, jnp.int32) if lim is None
           else lim.astype(jnp.int32))
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
    nb = Sp // BS
    if not lead or lead % BS or lead >= Sp:
        lead = Sp                   # no fresh tail to keep apart
    block = functools.partial(_key_block, bs=BS, lead=lead, blocks=nb)
    out = pl.pallas_call(
        functools.partial(_sparse_kernel, bq=BQ, heads=H, latent=latent,
                          bs=BS, lead=lead),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, Tp // BQ, nb),
            in_specs=[
                pl.BlockSpec((1, BQ * H, W),
                             lambda r, i, j, lim: (own(r), i, 0)),
                pl.BlockSpec((1, BS, W),
                             lambda r, i, j, lim: (r, block(lim, r, j), 0)),
                pl.BlockSpec((1, BQ, BS),
                             lambda r, i, j, lim: (r, i, block(lim, r, j))),
            ],
            out_specs=pl.BlockSpec((1, BQ * H, latent),
                                   lambda r, i, j, lim: (r, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((BQ * H, 1), jnp.float32),
                pltpu.VMEM((BQ * H, 1), jnp.float32),
                pltpu.VMEM((BQ * H, latent), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, Tp * H, latent), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024,
        ),
        name="mla_sparse_attention_tpu",
    )(lim, qf, kf, bf)
    return out.reshape(R, Tp, H, latent)[:, :T]
