"""Pallas TPU kernel: one decode step of the gated delta rule, the state's
decay, its write and its query in ONE pass.

XLA's form of ``ops/deltanet.py::delta_step`` walks the state three times
(read for what it holds under ``k``, write, read again for the query), and
the state is a quarter of a decode step's bytes.  Here a block of heads'
``S [dk, dv]`` is read once, decayed, asked what it holds under ``k``,
written ``beta`` of the way to ``v``, asked for ``q`` while it is still in
VMEM, and written back through ``input_output_aliases``: one read and one
write of the state a row, a layer and a step, over the live slots only.

``k`` and ``q`` are needed down the sublanes (``S``'s rows are the key's
channels): one ``[d, d]`` transpose a vector, as in
``ops/retention_kernel.py``.  The sums over the key's channels are sublane
reductions; everything else is elementwise on ``[dk, dv]`` tiles.

Grid ``(rows, head blocks)``, sequential.  Visits past the live rows repeat
the last live block (nothing is fetched or written for them) and are
skipped; with no live row at all the one block they all name is copied
through unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry

HEAD_BLOCK = 16     # value heads a grid step: 1 MB of state at width 128


def head_block(heads: int) -> int:
    return next(b for b in range(min(HEAD_BLOCK, heads), 0, -1)
                if heads % b == 0)


def check_deltanet_geometry(key_heads: int, value_heads: int, dk: int,
                            dv: int) -> None:
    """Raise :class:`UnsupportedKernelGeometry` for what Mosaic refuses, or
    the kernel does not do: the state's minor axis is the value width and
    must be whole 128-lane tiles, and the key's channels go down the
    sublanes by a square transpose, so the two widths are one."""
    why = None
    if dv % 128:
        why = "the value width must be a multiple of the 128 lanes"
    elif dk != dv:
        why = "the key and value widths must be equal"
    elif value_heads % key_heads:
        why = "the key heads must divide the value heads"
    elif head_block(value_heads) % 8:
        why = "the value heads must come in blocks of 8 (a sublane tile)"
    if why:
        raise UnsupportedKernelGeometry(
            "deltanet decode kernel: no TPU lowering for "
            f"{key_heads} key / {value_heads} value heads of width "
            f"{dk} / {dv}: {why}.  Serve this geometry with "
            "attn_backend='reference' explicitly, or extend the kernel.")


def _kernel(layer_ref, order_ref, count_ref, q_ref, k_ref, v_ref, a_ref,
            b_ref, s_ref, o_ref, so_ref, *, hb: int, d: int):
    del layer_ref, order_ref                 # read by the index maps
    n = pl.program_id(0)
    count = count_ref[0]

    @pl.when(n < count)
    def _live():
        def column(row):
            # [j, c] = u[j]: a vector down the sublanes, across every lane
            return jnp.broadcast_to(row, (d, d)).T

        for h in range(hb):                                  # static unroll
            at = pl.ds(h, 1)
            kc = column(k_ref[at, :])
            s = s_ref[h] * a_ref[at, :]                      # the decay
            held = jnp.sum(kc * s, axis=0, keepdims=True)    # [1, dv]
            s = s + kc * (b_ref[at, :] * (v_ref[at, :] - held))
            so_ref[h] = s
            o_ref[at, :] = jnp.sum(
                column(q_ref[at, :]) * s, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(count == 0, jnp.logical_and(
        n == 0, pl.program_id(1) == 0)))
    def _nothing_live():
        so_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def deltanet_decode_tpu(
    q,          # [B, H, dk] f32: normalised, times dk ** -0.5
    k,          # [B, H, dk] f32: normalised
    v,          # [B, H, dv] f32
    decay,      # [B, H] f32: exp(g)
    beta,       # [B, H] f32
    s_pool,     # [L, N, H, dk, dv] f32, N >= B: row b is slot b
    layer,      # which of the L layers (a traced index)
    order,      # [B] int32: the live rows first
    count,      # how many of them are live
    *,
    interpret: bool = False,
):
    """Returns ``(o [B, H, dv] f32, s_pool)``: ``S_t^T q`` of every live row
    (rows that are not live hold whatever was there), and the pool with the
    live slots' states advanced one token, in place."""
    B, H, dk = q.shape
    L, N, _, _, dv = s_pool.shape
    assert s_pool.shape[2:] == (H, dk, dv) and N >= B and dk == dv
    if not interpret:
        check_deltanet_geometry(H, H, dk, dv)
    hb = head_block(H)
    blocks = H // hb

    def visit(n, j, layer, order, count):
        """The (row, head block) a visit names: its own while the row is
        live, the last live one after."""
        dead = n >= count[0]
        row = order[jnp.clip(jnp.minimum(n, count[0] - 1), 0, B - 1)]
        return row, jnp.where(dead, blocks - 1, j)

    def vec_map(n, j, *pre):
        return visit(n, j, *pre) + (0,)

    def state_map(n, j, layer, order, count):
        return (layer[0],) + visit(n, j, layer, order, count) + (0, 0)

    vec = pl.BlockSpec((None, hb, dv), vec_map)
    state = pl.BlockSpec((None, None, hb, dk, dv), state_map)
    across = lambda a: jnp.broadcast_to(
        a.astype(jnp.float32)[..., None], (B, H, dv))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, blocks),
        in_specs=[vec, vec, vec, vec, vec, state],
        out_specs=[vec, state],
    )
    o, s_pool = pl.pallas_call(
        functools.partial(_kernel, hb=hb, d=dk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype)],
        # operand 8 (after the three prefetched scalars): the pool
        input_output_aliases={8: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        name="deltanet_decode_tpu",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), order.astype(jnp.int32),
        jnp.asarray(count, jnp.int32).reshape(1),
        q, k, v, across(decay), across(beta), s_pool,
    )
    return o, s_pool
