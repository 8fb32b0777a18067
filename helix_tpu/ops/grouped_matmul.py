"""Pallas TPU grouped matrix product for the dropless expert layer.

``out[rows of group g] = x[rows] @ W[layer, g]`` over rows SORTED by group
(``models/moe.py`` sorts the (token, choice) assignments by expert): what
``jax.lax.ragged_dot`` computes, for weights kept as a whole stack of
layers ``[n, X, K, N]`` and read as stored.

- **One grid step is one (row tile, group) visit against the group's whole
  ``[K, N]`` weight.**  The visits are listed group by group, a group's
  row tiles in order, and go in by scalar prefetch with the group offsets
  and the layer: the weight's block index is ``(layer, group)``, so a
  weight is fetched once per group however many row tiles the group spans,
  and the layer is picked from the stack without a slice being copied out.
  At most ``ceil(rows / tm) + X - 1`` visits a product.
- **int8 weights cross HBM as int8** and are converted in VMEM a K-slab at
  a time to the activations' dtype; the products accumulate in f32 and the
  group's per-output-channel scale multiplies the sum in the same step.
  Activations are never quantised.  Weights already in the activations'
  dtype skip the convert.
- **Gate and up in one call** (``w2``/``act``): both read the same rows, and
  ``act(x @ W1) * (x @ W2)`` is written in the activations' dtype, so the
  two f32 intermediates never reach HBM.  ``act`` without ``w2`` is the
  ungated expert's ``act(x @ W1)``, the same epilogue with one operand.
- A row tile shared by several groups is visited by each in turn; a visit
  stores only its own group's rows (the output tile stays in VMEM between
  consecutive visits).  Rows past the last group belong to nobody and hold
  whatever the buffer held: the caller masks them.
- ``row_tile`` picks ``tm`` from the static row count and group count of
  the call: the rows an expert gets on average decide it, nothing else.

On a v5e a layer's three products (64 experts of 2048 x 1408, int8) take
0.87 ms at 384 rows and 1.15 ms at 3,072: 78% and 59% of the time the
weights' bytes take at 819 GB/s (PERF.md section 6, PR 29).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry

ROW_TILES = (32, 64, 128)       # 128: the MXU's rows
VMEM_CAP = 100 * 2 ** 20        # of a v5e core's 128 MiB


def check_grouped_geometry(K: int, N: int):
    """Raise :class:`UnsupportedKernelGeometry` for what Mosaic refuses: the
    weight block is a group's whole ``[K, N]``, sliced in K for the convert
    and the product, so both widths have to be whole 128-lane tiles."""
    if K % 128 or N % 128:
        raise UnsupportedKernelGeometry(
            "grouped expert product kernel: no TPU lowering for "
            f"[{K}, {N}] expert weights: both widths must be multiples "
            "of the 128 lanes.  lax.ragged_dot serves this geometry."
        )


def glu(gate, up, act, limit: float = 0.0):
    """``act(gate) * up``; with ``limit`` the clamped form ``act(min(gate,
    limit)) * clip(up, -limit, limit)``."""
    if limit:
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
    return act(gate) * up


def relu2(x):
    """Squared ReLU, an ungated expert's activation."""
    return jnp.square(jax.nn.relu(x))


def row_tile(rows: int, groups: int) -> int:
    """The row tile for ``rows`` sorted rows over ``groups`` groups: the
    smallest of ``ROW_TILES`` that holds eight times a group's mean rows.
    A visit costs a weight's pass through the MXU whatever rows ride it,
    so the visits set the time and a tile should swallow most groups
    whole; past that a larger tile only streams more padding.  Measured
    on a v5e (PERF.md section 6, PR 29): 64 rows over 64 experts are
    fastest at 32, a decode segment of 384 rows at 64 (16 is 7% slower),
    a 3,072-row chunk at 128."""
    mean = -(-rows // max(groups, 1))
    return next((t for t in ROW_TILES if t >= 8 * mean), ROW_TILES[-1])


def visit_plan(group_sizes, rows: int, tm: int):
    """The (row tile, group) visits of a product, from ``group_sizes [X]``.

    Returns ``(offsets [X + 1], group [V], tile [V], count [1])`` int32,
    ``V = ceil(rows / tm) + X - 1`` the static bound; visits past ``count``
    repeat the last one (the same blocks: nothing is fetched for them) and
    are skipped by the kernel."""
    X = group_sizes.shape[0]
    V = -(-rows // tm) + X - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    cum = jnp.cumsum(tiles)
    count = cum[-1]
    v = jnp.minimum(jnp.arange(V, dtype=jnp.int32), jnp.maximum(count - 1, 0))
    group = jnp.minimum(
        jnp.sum((v[:, None] >= cum[None, :]).astype(jnp.int32), axis=1),
        X - 1)
    tile = first[group] + v - (cum - tiles)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile.astype(jnp.int32), count.reshape(1)


def _slab(K: int) -> int:
    """K rows of weight converted and multiplied at a time."""
    return next(s for s in (512, 256, 128) if K % s == 0)


def _kernel(layer_ref, offs_ref, group_ref, tile_ref, count_ref, x_ref,
            *refs, tm: int, n_w: int, scaled: bool, act, limit: float):
    del layer_ref   # read by the index maps
    w_refs = refs[:n_w]
    s_refs = refs[n_w:2 * n_w] if scaled else (None,) * n_w
    o_ref = refs[-1]
    v = pl.program_id(0)

    @pl.when(v < count_ref[0])
    def _visit():
        K = x_ref.shape[1]
        slab = _slab(K)
        bf16 = x_ref.dtype == jnp.bfloat16

        def product(w_ref, s_ref):
            acc = jnp.zeros((tm, w_ref.shape[1]), jnp.float32)
            for k0 in range(0, K, slab):   # static unroll
                w = w_ref[pl.ds(k0, slab), :]
                if w.dtype != x_ref.dtype:
                    # int8 bytes crossed HBM; the MXU gets bf16
                    w = w.astype(jnp.float32).astype(x_ref.dtype)
                acc = acc + jax.lax.dot_general(
                    x_ref[:, pl.ds(k0, slab)], w,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    # bf16 operands go to the MXU as they are, whatever
                    # jax_default_matmul_precision says
                    precision=jax.lax.Precision.DEFAULT if bf16 else None,
                )
            return acc if s_ref is None else acc * s_ref[...]

        y = product(w_refs[0], s_refs[0])
        if n_w == 2:
            y = glu(y, product(w_refs[1], s_refs[1]), act, limit)
        elif act is not None:
            y = act(y)          # an ungated expert: no second operand
        g = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (row >= offs_ref[g]) & (row < offs_ref[g + 1])
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("tm", "act", "limit", "out_dtype", "interpret"))
def grouped_matmul_tpu(
    x,              # [rows, K] sorted by group; rows past the last group
                    # belong to no group
    w,              # [n, X, K, N] a stack of layers' expert weights
    plan,           # visit_plan(group_sizes, rows, tm)
    layer,          # which of the n layers (a traced index)
    *,
    scale=None,     # [n, X, 1, N] f32 per-output-channel scales of w
    w2=None,        # a second weight like w: the call computes
    scale2=None,    # glu(x @ w, x @ w2, act, limit)
    act: Optional[Callable] = None,     # without w2: act(x @ w)
    limit: float = 0.0,
    tm: int,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    """Returns ``[rows, N]`` in ``out_dtype``."""
    rows, K = x.shape
    n, X, Kw, N = w.shape
    assert Kw == K and (w2 is None or act is not None)
    if not interpret:
        check_grouped_geometry(K, N)
    ws = tuple(a for a in (w, w2) if a is not None)
    ss = tuple(a for a in (scale, scale2) if a is not None)
    assert len(ss) in (0, len(ws))
    padded = -(-rows // tm) * tm
    if padded != rows:
        x = jnp.pad(x, ((0, padded - rows), (0, 0)))
    offsets, group, tile, count = plan
    V = group.shape[0]

    def x_map(v, layer, offs, group, tile, count):
        return tile[v], 0

    def w_map(v, layer, offs, group, tile, count):
        return layer[0], group[v], 0, 0

    out_size = jnp.dtype(out_dtype).itemsize
    vmem = (
        2 * tm * K * x.dtype.itemsize                      # x, two buffers
        + len(ws) * 2 * K * N * w.dtype.itemsize           # weights, two
        + len(ws) * _slab(K) * N * (4 + x.dtype.itemsize)  # a slab's convert
        + 2 * tm * N * out_size                            # out, two
        + (2 + len(ws)) * tm * N * 4                       # f32 sums
        + len(ss) * 2 * 8 * N * 4                          # scales
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(V,),
        in_specs=[pl.BlockSpec((tm, K), x_map)]
        + [pl.BlockSpec((None, None, K, N), w_map)] * len(ws)
        + [pl.BlockSpec((None, None, 1, N), w_map)] * len(ss),
        out_specs=pl.BlockSpec((tm, N), x_map),
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, tm=tm, n_w=len(ws), scaled=bool(ss), act=act,
            limit=limit),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((padded, N), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(int(vmem * 1.25) + (4 << 20), VMEM_CAP),
        ),
        name="grouped_matmul_tpu",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), offsets, group, tile,
        count, x, *ws, *ss,
    )
    return out[:rows]
