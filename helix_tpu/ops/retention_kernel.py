"""Pallas TPU kernel: one decode step of power retention, the state's update
and its query in ONE pass.

XLA's form of ``ops/retention.py::retention_step`` walks the state three
times (read for the update, write, read again for the query), and the state
is four fifths of a decode step's bytes.  Here a tile of ``S`` is read once,
scaled by the row's gate, given ``phi(k) v^T`` for its rows, multiplied by
the group's query heads' ``phi(q)`` rows while it is still in VMEM, and
written back through ``input_output_aliases``: one read and one write of the
state a row, a layer and a step, over the live slots only.

``phi`` is never built, in HBM or in VMEM.  In the held packing
(``ops/retention.py``) a tile is ``d/8 + 1`` blocks ``(a, b)`` of the
symmetric matrix ``k k^T``, and a vreg of the tile is row ``r`` of block
``a`` against the 8 columns of block ``b``, all ``d`` lanes of ``v``: its
update is ``k[8a + r] * (k[8b:8b+8] v^T)``, a scalar times an ``[8, d]``
slab of the outer product ``k v^T`` (built once a slot and head, 16 vregs at
width 128), and its query term is ``q[8a + r] * q[8b:8b+8]`` the same way.
The scalars come from SMEM copies of ``k`` and ``q``; the sublane-oriented
columns from one ``[d, d]`` transpose a vector.

Grid ``(rows, kv heads, tiles)``, sequential.  Visits past the live rows
repeat the last live block (nothing is fetched or written for them) and are
skipped; with no live row at all the one block they all name is copied
through unchanged.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry
from helix_tpu.ops.retention import BLOCK, held_rows, tile_rows

_SQRT2 = math.sqrt(2.0)


def check_retention_geometry(num_heads: int, num_kv_heads: int,
                             head_dim: int) -> None:
    """Raise :class:`UnsupportedKernelGeometry` for what Mosaic refuses: the
    state's minor axis is the value width and must be whole 128-lane tiles;
    the tiles pair block rows, so the width is a multiple of 16 anyway."""
    why = None
    if head_dim % 128:
        why = "the head width must be a multiple of the 128 lanes"
    elif num_heads % num_kv_heads:
        why = "the kv heads must divide the query heads"
    if why:
        raise UnsupportedKernelGeometry(
            "retention decode kernel: no TPU lowering for "
            f"{num_heads} query / {num_kv_heads} kv heads of width "
            f"{head_dim}: {why}.  Serve this geometry with "
            "attn_backend='reference' explicitly, or extend the kernel.")


def _kernel(layer_ref, order_ref, count_ref, q_ref, qs_ref, k_ref, ks_ref,
            v_ref, g_ref, s_ref, num_ref, o_ref, kv_ref, qb_ref, acc_ref,
            *, d: int, group: int):
    del layer_ref, order_ref                 # read by the index maps
    nb = d // BLOCK
    width = (nb + 1) * BLOCK                 # a row of the tile, in rows of S
    n, p = pl.program_id(0), pl.program_id(2)
    count = count_ref[0]

    @pl.when(n < count)
    def _live():
        @pl.when(p == 0)
        def _first_tile():
            # [j, c] = u[j]: a vector down the sublanes, across every lane
            def column(row):
                return jnp.broadcast_to(row, (d, d)).T

            kv_ref[...] = column(k_ref[...]) * v_ref[...]
            for h in range(group):
                qb_ref[h] = column(q_ref[pl.ds(h, 1), :])
            acc_ref[...] = jnp.zeros_like(acc_ref)

        gate = g_ref[...]                                    # [1, d]
        accs = [jnp.zeros((BLOCK, d), jnp.float32)] * group
        for s in range(nb + 1):                              # static unroll
            # the tile pairs block row p (its nb - p blocks first) with
            # block row nb - 1 - p
            first = s < nb - p
            a = jnp.where(first, p, nb - 1 - p)
            b = jnp.where(first, p + s, s - 1)
            w = jnp.where(a == b, 1.0, _SQRT2).astype(jnp.float32)
            at = pl.multiple_of(b * BLOCK, BLOCK)
            kvb = kv_ref[pl.ds(at, BLOCK), :] * w            # [8, d]
            part = [None] * group
            for r in range(BLOCK):
                rows = pl.ds(r * width + s * BLOCK, BLOCK)
                new = gate * s_ref[rows, :] + ks_ref[0, a * BLOCK + r] * kvb
                o_ref[rows, :] = new
                for h in range(group):
                    t = qs_ref[h, a * BLOCK + r] * new
                    part[h] = t if part[h] is None else part[h] + t
            accs = [
                acc + (qb_ref[h, pl.ds(at, BLOCK), :] * w) * part[h]
                for h, acc in enumerate(accs)]
        for h in range(group):
            acc_ref[h] += accs[h]

        @pl.when(p == nb // 2 - 1)
        def _last_tile():
            num_ref[...] = jnp.sum(acc_ref[...], axis=1)

    @pl.when(jnp.logical_and(count == 0, jnp.logical_and(
        n == 0, jnp.logical_and(pl.program_id(1) == 0, p == 0))))
    def _nothing_live():
        o_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def retention_decode_tpu(
    q,          # [B, KVH, G, d] f32, times head_dim ** -0.5
    k,          # [B, KVH, 1, d] f32
    v,          # [B, KVH, 1, d] f32
    gate,       # [B, KVH, 1, d] f32: the row's gate across the lanes
    s_pool,     # [L, N, KVH, D_held, d] f32, N >= B: row b is slot b
    layer,      # which of the L layers (a traced index)
    order,      # [B] int32: the live rows first
    count,      # how many of them are live
    *,
    interpret: bool = False,
):
    """Returns ``(num [B, KVH, G, d] f32, s_pool)``: ``phi(q)^T S_t`` of
    every live row (rows that are not live hold whatever was there), and the
    pool with the live slots' states advanced one token, in place."""
    B, KVH, G, d = q.shape
    L, N, _, F, _ = s_pool.shape
    assert F == held_rows(d) and N >= B
    if not interpret:
        check_retention_geometry(KVH * G, KVH, d)
    tiles, rows = d // (2 * BLOCK), tile_rows(d)

    def visit(n, h, p, layer, order, count):
        """The (row, kv head, tile) a visit names: its own while the row is
        live, the last live one after."""
        dead = n >= count[0]
        row = order[jnp.clip(jnp.minimum(n, count[0] - 1), 0, B - 1)]
        return (row, jnp.where(dead, KVH - 1, h),
                jnp.where(dead, tiles - 1, p))

    def vec_map(n, h, p, *pre):
        row, h, _ = visit(n, h, p, *pre)
        return row, h, 0, 0

    def state_map(n, h, p, layer, order, count):
        row, h, p = visit(n, h, p, layer, order, count)
        return layer[0], row, h, p, 0

    def vec(width, **kw):
        return pl.BlockSpec((None, None, width, d), vec_map, **kw)

    smem = dict(memory_space=pltpu.MemorySpace.SMEM)
    state = pl.BlockSpec((None, None, None, rows, d), state_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KVH, tiles),
        in_specs=[vec(G), vec(G, **smem), vec(1), vec(1, **smem), vec(1),
                  vec(1), state],
        out_specs=[vec(G), state],
        scratch_shapes=[
            pltpu.VMEM((d, d), jnp.float32),           # k v^T
            pltpu.VMEM((G, d, d), jnp.float32),        # q down the sublanes
            pltpu.VMEM((G, BLOCK, d), jnp.float32),    # the group's sums
        ],
    )
    num, s_pool = pl.pallas_call(
        functools.partial(_kernel, d=d, group=G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KVH, G, d), jnp.float32),
                   jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype)],
        # operand 9 (after the three prefetched scalars): the pool
        input_output_aliases={9: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        name="retention_decode_tpu",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), order.astype(jnp.int32),
        jnp.asarray(count, jnp.int32).reshape(1),
        q, q, k, k, v, gate, s_pool,
    )
    return num, s_pool
