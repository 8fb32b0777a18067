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


def head_block(heads: int, most: int = HEAD_BLOCK) -> int:
    return next(b for b in range(min(most, heads), 0, -1) if heads % b == 0)


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


def state_decode_call(live, vecs, pool, layer, order, count, *, hb: int,
                      name: str, interpret: bool = False):
    """The frame of a one-pass decode kernel over a pool of matrix states
    (this module's and ``ops/ssd_kernel.py``'s): ``vecs`` are ``[B, H, d]``
    float32 operands a row and head, ``pool [L, N, H, dk, d]`` float32 with
    ``N >= B`` (row ``b`` is slot ``b``).  Grid ``(rows, blocks of hb
    heads)``, sequential; ``layer``, ``order`` (the live rows first) and
    ``count`` go in by scalar prefetch, and a visit names its own (row, head
    block) while the row is live, the last live one after (nothing is fetched
    or written for it).  ``live(*vec refs, state ref, out ref, state out
    ref)`` is the body of a live visit, over blocks ``[hb, d]`` and ``[hb,
    dk, d]``.  The pool is aliased in and out: one read and one write of a
    live slot's state.  Returns ``(o [B, H, d] float32, pool)``."""
    B, H, d = vecs[0].shape
    blocks = H // hb

    def kernel(layer_ref, order_ref, count_ref, *refs):
        del layer_ref, order_ref             # read by the index maps
        s_ref, o_ref, so_ref = refs[-3:]
        n = pl.program_id(0)
        count = count_ref[0]

        @pl.when(n < count)
        def _live():
            live(*refs)

        @pl.when(jnp.logical_and(count == 0, jnp.logical_and(
            n == 0, pl.program_id(1) == 0)))
        def _nothing_live():
            so_ref[...] = s_ref[...]

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

    vec = pl.BlockSpec((None, hb, d), vec_map)
    state = pl.BlockSpec((None, None, hb) + pool.shape[3:], state_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, blocks),
        in_specs=[vec] * len(vecs) + [state],
        out_specs=[vec, state],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, d), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool, behind the three prefetched scalars and the vectors
        input_output_aliases={3 + len(vecs): 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        name=name,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), order.astype(jnp.int32),
        jnp.asarray(count, jnp.int32).reshape(1), *vecs, pool,
    )


def _live(q_ref, k_ref, v_ref, a_ref, b_ref, s_ref, o_ref, so_ref, *,
          hb: int, d: int):
    def column(row):
        # [j, c] = u[j]: a vector down the sublanes, across every lane
        return jnp.broadcast_to(row, (d, d)).T

    for h in range(hb):                                      # static unroll
        at = pl.ds(h, 1)
        kc = column(k_ref[at, :])
        s = s_ref[h] * a_ref[at, :]                          # the decay
        held = jnp.sum(kc * s, axis=0, keepdims=True)        # [1, dv]
        s = s + kc * (b_ref[at, :] * (v_ref[at, :] - held))
        so_ref[h] = s
        o_ref[at, :] = jnp.sum(
            column(q_ref[at, :]) * s, axis=0, keepdims=True)


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
    across = lambda a: jnp.broadcast_to(
        a.astype(jnp.float32)[..., None], (B, H, dv))
    return state_decode_call(
        functools.partial(_live, hb=hb, d=dk),
        (q, k, v, across(decay), across(beta)), s_pool, layer, order, count,
        hb=hb, name="deltanet_decode_tpu", interpret=interpret)


CHUNK_HEAD_BLOCK = 8    # value heads a grid step of the chunk kernel: 1.4 MB in
# what an entry of the chunk kernel's table is to its row and its state block
FIRST, WRITE, FROM_STATE, OPEN = 1, 2, 4, 8


def _chunk_kernel(layer_ref, slot_ref, flag_ref, count_ref, sv_ref, sk_ref,
                  qg_ref, qk_ref, kd_ref, el_ref, s_ref, c_ref, o_ref, so_ref,
                  co_ref, s_scr, *, hb: int):
    del layer_ref, slot_ref                  # read by the index maps
    e = pl.program_id(1)
    flags = flag_ref[e]
    live = e < count_ref[0]
    on = lambda flag: flags & flag != 0
    dot = functools.partial(
        jax.lax.dot_general, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    nn = (((1,), (0,)), ((), ()))
    tn = (((0,), (0,)), ((), ()))

    @pl.when(e == 0)
    def _the_row_under_way():
        s_scr[...] = c_ref[...]

    @pl.when(on(OPEN))
    def _a_state_block_opens():
        # written back whatever follows: unchanged, unless its row ends here
        so_ref[...] = s_ref[...]

    @pl.when(jnp.logical_and(on(FIRST), on(FROM_STATE)))
    def _a_row_continues_from_its_slot():
        s_scr[...] = s_ref[...]

    @pl.when(jnp.logical_and(on(FIRST), jnp.logical_not(on(FROM_STATE))))
    def _a_row_starts():
        s_scr[...] = jnp.zeros_like(s_scr)

    @pl.when(live)
    def _a_chunk():
        for h in range(hb):                                  # static unroll
            s = s_scr[h]
            new = sv_ref[h] - dot(sk_ref[h], s, nn)          # the writes
            o_ref[h] = dot(qg_ref[h], s, nn) + dot(qk_ref[h], new, nn)
            s_scr[h] = el_ref[pl.ds(h, 1), :] * s + dot(kd_ref[h], new, tn)

    @pl.when(on(WRITE))
    def _a_row_ends():
        so_ref[...] = s_scr[...]

    @pl.when(e == pl.num_programs(1) - 1)
    def _hand_on():
        co_ref[...] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def deltanet_chunk_tpu(
    sol_v,      # [n, H, C, dv] f32   } ops/deltanet.py::state_free of the
    sol_k,      # [n, H, C, dk] f32   } chunks of ``table``, in its order
    qg,         # [n, H, C, dk] f32
    qk,         # [n, H, C, C] f32
    kd,         # [n, H, C, dk] f32
    elast,      # [n, H] f32
    s_pool,     # [L, N, H, dk, dv] f32
    s_row,      # [H, dk, dv] f32: the state of the row under way at entry 0
    layer,      # which of the L layers (a traced index)
    table,      # n entries of ops/deltanet.py::chunk_table
    count,      # how many of them are some row's: they come first
    *,
    interpret: bool = False,
):
    """The half of the chunked delta rule that reads the state: the table's
    entries in order, three products a head against ``S``, which stays in
    VMEM from a row's first chunk to its last: read from ``s_pool[layer,
    slot]`` at the first (zeros for a row that starts there), written at the
    last, in place; a row whose first chunk lay before this table continues
    from ``s_row``, and the state of a row whose last lies behind it is
    handed on.  Returns ``(o [n, H, C, dv] f32, s_pool, s_row)``; entries
    past the rows' ends hold whatever was there.

    Grid ``(head blocks, entries)``, sequential.  An entry of a row with no
    slot, and entries past the rows' ends, name the state block of the last
    row before them that has one (nothing is fetched or written back for
    them: a row without a slot starts from zeros and its state goes
    nowhere).  A block is copied through as it opens, so one whose row does
    not end here, or that no row owns, goes back as it came."""
    n, H, C, dv = sol_v.shape
    dk = sol_k.shape[-1]
    assert s_pool.shape[2:] == (H, dk, dv) == s_row.shape
    if not interpret:
        check_deltanet_geometry(H, H, dk, dv)
    hb = head_block(H, CHUNK_HEAD_BLOCK)
    at = jnp.arange(n, dtype=jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    slotted = jnp.sum(table["has_slot"]).astype(jnp.int32)
    named = table["slot"][jnp.minimum(at, jnp.maximum(slotted - 1, 0))]
    flags = (FIRST * table["first"] + WRITE * table["write"]
             + FROM_STATE * table["from_state"]
             + OPEN * ((at == 0) | (table["first"] & table["has_slot"]))
             ).astype(jnp.int32)

    def entry(j, e, layer, slot, flags, count):
        return jnp.minimum(e, jnp.maximum(count[0] - 1, 0)), j

    def mat_map(j, e, *pre):
        return entry(j, e, *pre) + (0, 0)

    def vec_map(j, e, *pre):
        return entry(j, e, *pre) + (0,)

    def state_map(j, e, layer, slot, flags, count):
        return layer[0], slot[e], j, 0, 0

    mat = lambda w: pl.BlockSpec((None, hb, C, w), mat_map)
    state = pl.BlockSpec((None, None, hb, dk, dv), state_map)
    row = pl.BlockSpec((hb, dk, dv), lambda j, e, *pre: (j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(H // hb, n),
        in_specs=[mat(dv), mat(dk), mat(dk), mat(C), mat(dk),
                  pl.BlockSpec((None, hb, dv), vec_map), state, row],
        out_specs=[mat(dv), state, row],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_chunk_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, H, C, dv), jnp.float32),
                   jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype),
                   jax.ShapeDtypeStruct(s_row.shape, s_row.dtype)],
        # operand 10 (after the four prefetched scalars): the pool
        input_output_aliases={10: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        name="deltanet_chunk_tpu",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), named, flags,
        count.reshape(1),
        sol_v, sol_k, qg, qk, kd,
        jnp.broadcast_to(elast[..., None], (n, H, dv)), s_pool, s_row,
    )
