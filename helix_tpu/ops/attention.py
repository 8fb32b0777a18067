"""Attention: XLA reference implementation + Pallas TPU flash kernel.

The reference framework never implements attention itself — it is inside the
vLLM CUDA containers its compose profiles launch (``SURVEY.md`` §2.2).  Here
it is owned code:

- ``mha_reference`` — pure-XLA multi-head attention with GQA, causal and
  packed-segment masking.  Used on CPU (tests) and as the numerics oracle.
- ``flash_attention`` — Pallas TPU kernel, online-softmax tiling so the
  [S, S] score matrix never materialises in HBM; fp32 accumulation on the
  MXU; grid iterates kv-blocks innermost with VMEM scratch carrying the
  running (max, sum, acc) between iterations.

Decode-time paged attention over the KV cache lives in
``helix_tpu.ops.paged`` (ragged paged attention per PAPERS.md).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _repeat_kv(k, num_q_heads):
    """[B, S, KVH, D] -> [B, S, H, D] for GQA in the reference path."""
    kvh = k.shape[-2]
    if kvh == num_q_heads:
        return k
    return jnp.repeat(k, num_q_heads // kvh, axis=-2)


def mha_reference(
    q,
    k,
    v,
    *,
    causal: bool = True,
    q_positions=None,
    kv_positions=None,
    q_segment_ids=None,
    kv_segment_ids=None,
    logits_soft_cap: Optional[float] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
):
    """Numerics oracle. q: [B, Sq, H, D]; k/v: [B, Skv, KVH, D].

    ``q_positions``/``kv_positions`` make causal masking correct for ragged
    prefill where query block i sits at an arbitrary absolute position.
    ``segment_ids`` mask cross-sequence attention in packed batches.
    ``window``: a query at position i sees keys ``i - window < j <= i`` (the
    query's own among them); it needs ``causal``.
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    k = _repeat_kv(k, H)
    v = _repeat_kv(v, H)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
    mask = jnp.ones((B, 1, Sq, Skv), dtype=bool)
    if causal:
        qp = (
            q_positions
            if q_positions is not None
            else jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
        )
        kp = (
            kv_positions
            if kv_positions is not None
            else jnp.broadcast_to(jnp.arange(Skv)[None], (B, Skv))
        )
        mask = mask & (qp[:, None, :, None] >= kp[:, None, None, :])
        if window is not None:
            mask = mask & (
                qp[:, None, :, None] - kp[:, None, None, :] < window)
    elif window is not None:
        raise ValueError("a window needs causal attention")
    if q_segment_ids is not None and kv_segment_ids is not None:
        mask = mask & (
            q_segment_ids[:, None, :, None] == kv_segment_ids[:, None, None, :]
        )
    logits = jnp.where(mask, logits, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU flash attention
# ---------------------------------------------------------------------------


def _flash_kernel(
    qpos_ref,   # VMEM [1, 1, BQ] int32 — this q block's absolute positions
    kpos_ref,   # VMEM [1, 1, BK]
    qseg_ref,   # VMEM [1, 1, BQ]
    kseg_ref,   # VMEM [1, 1, BK]
    q_ref,      # [1, 1, BQ, D]  (layout [B, H, S, D])
    k_ref,      # [1, 1, BK, D]
    v_ref,
    o_ref,      # [1, 1, BQ, D]
    m_scr,      # VMEM [BQ, 1] fp32
    l_scr,      # VMEM [BQ, 1] fp32
    acc_scr,    # VMEM [BQ, D] fp32
    *,
    scale: float,
    causal: bool,
    use_segments: bool,
    block_q: int,
    block_kv: int,
    soft_cap: Optional[float],
    window: Optional[int],
):
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qp = qpos_ref[0, 0, :]
    kp = kpos_ref[0, 0, :]

    if causal:
        # Causal block skipping: a kv block wholly above the diagonal
        # (every key position beyond every query position) contributes
        # nothing — skip its matmuls entirely. Computed from the position
        # blocks, so it is exact for ragged/chunked prefill too; for the
        # default arange positions it degenerates to the classic
        # lower-triangle grid walk (~2x fewer MXU FLOPs at long S).
        run = jnp.max(qp) >= jnp.min(kp)
        if window is not None:
            # ... and so does one wholly behind every query's window
            run = run & (jnp.min(qp) - jnp.max(kp) < window)
    else:
        run = True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        # bf16 operands have one MXU mode; a process-wide "highest"
        # matmul precision (the test suite's) would ask Mosaic for an fp32
        # contraction of bf16 vectors, which it refuses
        prec = jax.lax.Precision.DEFAULT if q.dtype == jnp.bfloat16 else None
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        ) * scale
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)

        mask = jnp.ones((block_q, block_kv), dtype=bool)
        if causal:
            mask = mask & (qp[:, None] >= kp[None, :])
            if window is not None:
                mask = mask & (qp[:, None] - kp[None, :] < window)
        if use_segments:
            mask = mask & (
                qseg_ref[0, 0, :][:, None] == kseg_ref[0, 0, :][None, :]
            )
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)

        m_prev = m_scr[:]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype),
            v_ref[0, 0, :, :],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec,
        )
        m_scr[:] = m_new
        l_scr[:] = l_new
        acc_scr[:] = acc

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros, not NaN
        o_ref[0, 0, :, :] = (acc_scr[:] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal",
        "scale",
        "logits_soft_cap",
        "block_q",
        "block_kv",
        "interpret",
        "window",
    ),
)
def flash_attention(
    q,
    k,
    v,
    *,
    q_positions=None,
    kv_positions=None,
    q_segment_ids=None,
    kv_segment_ids=None,
    causal: bool = True,
    scale: Optional[float] = None,
    logits_soft_cap: Optional[float] = None,
    block_q: int = 256,
    block_kv: int = 256,
    interpret: bool = False,
    window: Optional[int] = None,
):
    """Flash attention for prefill. q: [B, Sq, H, D]; k/v: [B, Skv, KVH, D].
    ``window``: sliding-window attention beside the causal and segment
    masks (``mha_reference``); kv blocks wholly behind a query block's
    window are skipped like those wholly ahead of it.

    GQA is handled in the grid index map (each q head reads its kv group's
    block — no materialised ``repeat``).  Sequences shorter than the block
    size fall through with single-block grids; callers pad S to a multiple
    of the block (the engine pads to page size anyway).
    """
    B, Sq, H, D = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    group = H // KVH
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if window is not None and not causal:
        raise ValueError("a window needs causal attention")
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    if Sq % block_q or Skv % block_kv:
        raise ValueError(
            f"seq lens ({Sq}, {Skv}) must be multiples of blocks "
            f"({block_q}, {block_kv})"
        )
    nq, nk = Sq // block_q, Skv // block_kv

    def bcast_i32(x, default, shape):
        if x is None:
            x = default
        return jnp.broadcast_to(x, shape).astype(jnp.int32)

    # [B, 1, S] so position/segment blocks satisfy TPU tiling (last two block
    # dims = (1, block) with the 1 equal to the full middle dim).
    qpos = bcast_i32(q_positions, jnp.arange(Sq)[None], (B, Sq))[:, None, :]
    kpos = bcast_i32(kv_positions, jnp.arange(Skv)[None], (B, Skv))[:, None, :]
    use_segments = q_segment_ids is not None
    qseg = bcast_i32(q_segment_ids, 0, (B, Sq))[:, None, :]
    kseg = bcast_i32(kv_segment_ids, 0, (B, Skv))[:, None, :]

    # Kernel operates in [B, H, S, D]: the blocked (S, D) pair lands in the
    # last two dims as TPU tiling requires.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        use_segments=use_segments,
        block_q=block_q,
        block_kv=block_kv,
        soft_cap=logits_soft_cap,
        window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q), lambda b, h, i, j: (b, 0, i)),   # qpos
            pl.BlockSpec((1, 1, block_kv), lambda b, h, i, j: (b, 0, j)),  # kpos
            pl.BlockSpec((1, 1, block_q), lambda b, h, i, j: (b, 0, i)),   # qseg
            pl.BlockSpec((1, 1, block_kv), lambda b, h, i, j: (b, 0, j)),  # kseg
            pl.BlockSpec(
                (1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, D), lambda b, h, i, j: (b, h // group, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, D), lambda b, h, i, j: (b, h // group, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qpos, kpos, qseg, kseg, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


def resolve_backend(backend: Optional[str] = None) -> str:
    """``backend`` if given, else the one the process' devices call for:
    the Pallas kernels on a TPU, the XLA references on a CPU.  Keys off
    the process' first device (works under tracing, where per-array
    ``.devices()`` is unavailable).  Any other platform has no kernel
    here and is an error rather than a silent reference run."""
    if backend is not None:
        return backend
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return "pallas"
    if platform == "cpu":
        return "reference"
    raise RuntimeError(
        f"no attention backend for platform {platform!r}: pass "
        "backend='pallas' or backend='reference' explicitly"
    )


def head_shards(mesh) -> int:
    """How many ways ``mesh`` splits attention heads (its ``tp`` axis,
    the mesh axis of the "heads"/"cache_heads" logical axes)."""
    if mesh is None or "tp" not in mesh.axis_names:
        return 1
    return mesh.shape["tp"]


def over_heads(fn, mesh, in_specs, out_specs):
    """Mosaic kernels cannot be partitioned by XLA: under a mesh that
    shards heads each device runs the kernel on its own heads inside a
    ``shard_map`` (attention needs nothing from another head)."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def attention(
    q,
    k,
    v,
    *,
    backend: Optional[str] = None,
    mesh=None,
    **kwargs,
):
    """Dispatch: Pallas on TPU, reference on CPU or when asked for by name
    (``resolve_backend``).  ``mesh``: the mesh q/k/v are sharded over, so
    that the kernel runs per head shard."""
    if resolve_backend(backend) == "pallas":
        names = (
            "q_positions", "kv_positions", "q_segment_ids",
            "kv_segment_ids",
        )
        arrays = {
            n: kwargs.pop(n) for n in names if kwargs.get(n) is not None
        }

        def call(q, k, v, arrays):
            return flash_attention(q, k, v, **arrays, **kwargs)

        if head_shards(mesh) > 1:
            heads = P(None, None, "tp", None)
            call = over_heads(call, mesh, (heads, heads, heads, P()), heads)
        return call(q, k, v, arrays)
    kwargs.pop("block_q", None)
    kwargs.pop("block_kv", None)
    kwargs.pop("interpret", None)
    return mha_reference(q, k, v, **kwargs)
