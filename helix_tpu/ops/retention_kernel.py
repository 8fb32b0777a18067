"""Pallas TPU kernels for power retention: one decode step (the state's
update and its query in ONE pass, or its query alone), and the half of the
chunked form that touches the state (``retention_chunk_tpu``, further down: a
row of fresh tokens against its slot's state, read once and written once).

**The decode step** (``retention_decode_tpu``).
XLA's form of ``ops/retention.py::retention_step`` walks the state three
times (read for the update, write, read again for the query), and the state
is four fifths of a decode step's bytes.  Here a tile of ``S`` is read once,
scaled by the row's gate, given ``phi(k) v^T`` for its rows, multiplied by
the group's query heads' ``phi(q)`` rows while it is still in VMEM, and
written back through ``input_output_aliases``: one read and one write of the
state a row, a layer and a step, over the live slots only.

**A fused window writes once.**  The recurrence is linear, so the steps of a
window of decode steps that are not its last need ``phi(q)^T S_0`` alone
(``ops/retention.py::retention_window_step`` adds the window's own tokens by
their scores): with ``commit`` false (data, a prefetched scalar: one program)
the kernel streams the same tiles, multiplies, and writes NOTHING: every
visit names one output block, which goes back as it came.  The window's last
step commits all its ``M`` tokens at once, ``S = G S + sum_j phi(k_j) (G_j
v_j)^T``, and queries what it wrote; ``M = 1`` is the step that stands alone.

``phi`` is never built, in HBM or in VMEM.  In the held packing
(``ops/retention.py``) a tile is ``d/8 + 1`` blocks ``(a, b)`` of the
symmetric matrix ``k k^T``, and a vreg of the tile is row ``r`` of block
``a`` against the 8 columns of block ``b``, all ``d`` lanes of ``v``: its
update is ``k[8a + r] * (k[8b:8b+8] v^T)``, a number times an ``[8, d]``
slab of the outer product ``k v^T`` (built once a slot and head, 16 vregs at
width 128), and its query term is ``q[8a + r] * q[8b:8b+8]`` the same way.
Both factors come from one ``[d, d]`` transpose a vector (the vector down
the sublanes, across every lane): the slab is its rows ``8b..8b+8``, the
number is its row ``8a + r`` spread over the sublanes.  (NOT a scalar from
an SMEM copy of the vector at a traced index: that is 27 scalar operations a
vreg of state, and the scalar unit, not the bytes, then sets the pass's time.)

Grid ``(rows, kv heads, tiles)``, sequential.  Visits past the live rows
repeat the last live block (nothing is fetched or written for them) and are
skipped; with no live row at all the one block they all name is copied
through unchanged.

**A row of fresh tokens** (``retention_chunk_tpu``).  There the two
products are matrix products and ``phi`` IS built, but only in VMEM: a tile
of ``phi(Q)^T`` or ``phi(K)^T`` for 128 tokens at a time, features down the
sublanes and tokens across the lanes, where an 8-row slab is the
sublane-aligned slice ``u^T[8b:8b+8, :]`` times the one row ``u^T[8a + r,
:]`` times the block's weight: whole vregs, one multiply each.
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
            "retention kernels: no TPU lowering for "
            f"{num_heads} query / {num_kv_heads} kv heads of width "
            f"{head_dim}: {why}.  Serve this geometry with "
            "attn_backend='reference' explicitly, or extend the kernel.")


def _as_i32(a):
    """A prefetched scalar or index vector: int32, one axis."""
    return jnp.asarray(a, jnp.int32).reshape(-1)


def _kernel(layer_ref, order_ref, count_ref, commit_ref, q_ref, k_ref, v_ref,
            g_ref, s_ref, num_ref, o_ref, kb_ref, kv_ref, qb_ref, acc_ref,
            *, d: int, group: int, terms: int):
    del layer_ref, order_ref                 # read by the index maps
    nb = d // BLOCK
    width = (nb + 1) * BLOCK                 # a row of the tile, in rows of S
    n, p = pl.program_id(0), pl.program_id(2)
    count = count_ref[0]
    commits = commit_ref[0] > 0
    live = n < count

    def column(row):
        # [j, c] = u[j]: a vector down the sublanes, across every lane
        return jnp.broadcast_to(row, (d, d)).T

    def across(rows, r):
        """Row ``r`` of each ``[8, d]`` block of ``rows [n, 8, d]`` over all 8
        sublanes: with a vector down the sublanes, ``u[8 a + r]`` across a
        whole vreg."""
        return jnp.broadcast_to(rows[:, r:r + 1], rows.shape)

    def a_tile(write: bool):
        """The tile against the group's queries; ``write``: scaled by the
        row's gate and given the window's outer products first, and stored.
        (The heads and the window's tokens are ONE array each: a program
        holds this body twice, and a step program is lowered a bucket at a
        time, so every traced operation is paid in set-up.)"""
        @pl.when(p == 0)
        def _first_tile():
            if write:
                for j in range(terms):
                    kb_ref[j] = column(k_ref[pl.ds(j, 1), :])
                    kv_ref[j] = kb_ref[j] * v_ref[pl.ds(j, 1), :]
            for h in range(group):
                qb_ref[h] = column(q_ref[pl.ds(h, 1), :])
            acc_ref[...] = jnp.zeros_like(acc_ref)

        gate = g_ref[...]                                    # [1, d]
        acc = None
        for s in range(nb + 1):                              # static unroll
            # the tile pairs block row p (its nb - p blocks first) with
            # block row nb - 1 - p
            first = s < nb - p
            a = jnp.where(first, p, nb - 1 - p)
            b = jnp.where(first, p + s, s - 1)
            w = jnp.where(a == b, 1.0, _SQRT2).astype(jnp.float32)
            up = pl.ds(pl.multiple_of(a * BLOCK, BLOCK), BLOCK)
            at = pl.ds(pl.multiple_of(b * BLOCK, BLOCK), BLOCK)
            qa = qb_ref[:, up, :]                            # [G, 8, d]
            if write:
                ka = kb_ref[:, up, :]                        # [M, 8, d]
                kvb = kv_ref[:, at, :] * w
            part = None
            for r in range(BLOCK):
                rows = pl.ds(r * width + s * BLOCK, BLOCK)
                new = s_ref[rows, :]
                if write:
                    new = gate * new + jnp.sum(across(ka, r) * kvb, axis=0)
                    o_ref[rows, :] = new
                t = across(qa, r) * new
                part = t if part is None else part + t
            t = (qb_ref[:, at, :] * w) * part
            acc = t if acc is None else acc + t
        acc_ref[...] += acc

        @pl.when(p == nb // 2 - 1)
        def _last_tile():
            num_ref[...] = jnp.sum(acc_ref[...], axis=1)

    @pl.when(jnp.logical_and(live, commits))
    def _commits():
        a_tile(True)

    @pl.when(jnp.logical_and(live, jnp.logical_not(commits)))
    def _reads():
        a_tile(False)

    # every visit of a step that writes nothing names ONE output block, the
    # first visit's: copied through once, it goes back as it came
    @pl.when(jnp.logical_and(
        jnp.logical_or(count == 0, jnp.logical_not(commits)),
        jnp.logical_and(n == 0, jnp.logical_and(
            pl.program_id(1) == 0, p == 0))))
    def _nothing_written():
        o_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def retention_decode_tpu(
    q,          # [B, KVH, G, d] f32, times head_dim ** -0.5
    k,          # [B, KVH, M, d] f32: the window's tokens (zeros: no term)
    v,          # [B, KVH, M, d] f32, each times the gates after its token
    gate,       # [B, KVH, 1, d] f32: the window's whole gate across the lanes
    s_pool,     # [L, N, KVH, D_held, d] f32, N >= B: row b is slot b
    layer,      # which of the L layers (a traced index)
    order,      # [B] int32: the live rows first
    count,      # how many of them are live
    commit=True,  # (traced) False: the state is read and nothing is written
    *,
    interpret: bool = False,
):
    """Returns ``(num [B, KVH, G, d] f32, s_pool)`` for every live row (rows
    that are not live hold whatever was there).  ``commit``: the live slots'
    states advanced by the window's ``M`` tokens in place, ``S = gate * S +
    sum_j phi(k_j) v_j^T`` (``M = 1``: one decode step), and ``num = phi(q)^T
    S``.  Not ``commit``: ``num = phi(q)^T S`` of the state as it stands, the
    pool bit for bit what it was (one tile is written back as it came)."""
    B, KVH, G, d = q.shape
    M = k.shape[2]
    L, N, _, F, _ = s_pool.shape
    assert F == held_rows(d) and N >= B
    if not interpret:
        check_retention_geometry(KVH * G, KVH, d)
    tiles, rows = d // (2 * BLOCK), tile_rows(d)

    def visit(n, h, p, order, count):
        """The (row, kv head, tile) a visit names: its own while the row is
        live, the last live one after."""
        dead = n >= count[0]
        row = order[jnp.clip(jnp.minimum(n, count[0] - 1), 0, B - 1)]
        return (row, jnp.where(dead, KVH - 1, h),
                jnp.where(dead, tiles - 1, p))

    def vec_map(n, h, p, layer, order, count, commit):
        row, h, _ = visit(n, h, p, order, count)
        return row, h, 0, 0

    def state_in(n, h, p, layer, order, count, commit):
        return (layer[0],) + visit(n, h, p, order, count) + (0,)

    def state_out(n, h, p, layer, order, count, commit):
        # a step that writes nothing names the first visit's block throughout
        row, h, p = (
            jnp.where(commit[0] > 0, here, first) for here, first in zip(
                visit(n, h, p, order, count), visit(0, 0, 0, order, count)))
        return layer[0], row, h, p, 0

    def vec(width):
        return pl.BlockSpec((None, None, width, d), vec_map)

    state = lambda index: pl.BlockSpec((None, None, None, rows, d), index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, KVH, tiles),
        in_specs=[vec(G), vec(M), vec(M), vec(1), state(state_in)],
        out_specs=[vec(G), state(state_out)],
        scratch_shapes=[
            pltpu.VMEM((M, d, d), jnp.float32),        # k_j down the sublanes
            pltpu.VMEM((M, d, d), jnp.float32),        # k_j v_j^T
            pltpu.VMEM((G, d, d), jnp.float32),        # q down the sublanes
            pltpu.VMEM((G, BLOCK, d), jnp.float32),    # the group's sums
        ],
    )
    num, s_pool = pl.pallas_call(
        functools.partial(_kernel, d=d, group=G, terms=M),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KVH, G, d), jnp.float32),
                   jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype)],
        # operand 8 (after the four prefetched scalars): the pool
        input_output_aliases={8: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        name="retention_decode_tpu",
    )(
        _as_i32(layer), _as_i32(order), _as_i32(count), _as_i32(commit),
        q, k, v, gate, s_pool,
    )
    return num, s_pool


TOKENS = 128        # tokens a block of the chunk kernel: the lanes of a vreg


def _chunk_kernel(layer_ref, slot_ref, t0_ref, qlen_ref, hist_ref, live_ref,
                  q_ref, k_ref, vo_ref, decay_ref, s_ref, num_ref, o_ref,
                  phiq_ref, phik_ref, *, d: int, group: int):
    del layer_ref, slot_ref                  # read by the index maps
    nb = d // BLOCK
    width = (nb + 1) * BLOCK                 # a row of the tile, in rows of S
    r, p = pl.program_id(1), pl.program_id(2)
    n_hist, n_live = hist_ref[0], live_ref[0]
    lo = t0_ref[r]
    hi = lo + qlen_ref[r]
    dot = functools.partial(
        jax.lax.dot_general, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    nn = (((1,), (0,)), ((), ()))
    tn = (((0,), (0,)), ((), ()))

    def phi_t(u_ref, at, out_ref, cols):
        """``phi(u)^T`` of this tile for the 128 tokens of ``u_ref[at]``
        (``[d, 128]``: channels down the sublanes), into ``out_ref[:,
        cols]``: slab ``(i, s)`` is the 8 channels of block ``b`` times
        channel ``8 a + i``, times the block's weight."""
        def across(a):
            rows = u_ref[at + (pl.ds(pl.multiple_of(a * BLOCK, BLOCK),
                                     BLOCK), slice(None))]
            return [jnp.broadcast_to(rows[i:i + 1], rows.shape)
                    for i in range(BLOCK)]

        # the tile pairs block row p (its nb - p blocks first) with block
        # row nb - 1 - p
        upper, lower = across(p), across(nb - 1 - p)
        for s in range(nb + 1):                              # static unroll
            first = s < nb - p
            a = jnp.where(first, p, nb - 1 - p)
            b = jnp.where(first, p + s, s - 1)
            w = jnp.where(a == b, 1.0, _SQRT2).astype(jnp.float32)
            ub = u_ref[at + (pl.ds(pl.multiple_of(b * BLOCK, BLOCK), BLOCK),
                             slice(None))] * w
            for i in range(BLOCK):
                out_ref[pl.ds(i * width + s * BLOCK, BLOCK), cols] = (
                    ub * jnp.where(first, upper[i], lower[i]))

    @pl.when(jnp.logical_and(r == 0, p == 0))
    def _a_kv_head_opens():
        num_ref[...] = jnp.zeros_like(num_ref)

    @pl.when(r < n_live)
    def _a_row():
        from_state = r < n_hist

        @pl.when(from_state)
        def _decayed():
            o_ref[...] = decay_ref[...] * s_ref[...]

        @pl.when(jnp.logical_not(from_state))
        def _from_zeros():
            o_ref[...] = jnp.zeros_like(o_ref)

        def block(tb, carry):
            token = tb * TOKENS + jax.lax.broadcasted_iota(
                jnp.int32, (TOKENS, 1), 0)
            mine = jnp.logical_and(token >= lo, token < hi)  # [128, 1]

            @pl.when(from_state)
            def _what_the_state_holds():
                for g in range(group):
                    phi_t(q_ref, (tb, g), phiq_ref,
                          pl.ds(g * TOKENS, TOKENS))
                held = dot(phiq_ref[...], s_ref[...], tn)    # [G * 128, d]
                for g in range(group):
                    num_ref[tb, g] += jnp.where(
                        mine, held[g * TOKENS:(g + 1) * TOKENS], 0.0)

            phi_t(k_ref, (tb,), phik_ref, slice(None))
            o_ref[...] += dot(
                phik_ref[...], jnp.where(mine, vo_ref[tb], 0.0), nn)
            return carry

        jax.lax.fori_loop(lo // TOKENS, (hi - 1) // TOKENS + 1, block, 0)

    @pl.when(jnp.logical_and(n_live == 0, jnp.logical_and(
        pl.program_id(0) == 0, jnp.logical_and(r == 0, p == 0))))
    def _nothing_live():
        o_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def retention_chunk_tpu(
    q,          # [KVH, NB, G, d, 128] f32: q^T (times head_dim ** -0.5), 128
                #   tokens of the flat axis a block
    k,          # [KVH, NB, d, 128] f32: k^T
    vo,         # [KVH, NB, 128, d] f32: v times the decay from its token to
                #   its row's last
    decay,      # [R, KVH, 1, d] f32: a row's whole decay across the lanes
    s_pool,     # [L, N, KVH, D_held, d] f32
    layer,      # which of the L layers (a traced index)
    slot,       # [R] int32, inside the pool  } rows that continue from
    t0,         # [R] int32: first token      } their slot first, then rows
    qlen,       # [R] int32: tokens           } from zeros, then the rest
    n_hist,     # how many rows continue from their slot's state
    n_live,     # how many rows are live (those, and the rows from zeros)
    *,
    interpret: bool = False,
):
    """The half of the chunked form that touches the state, a live row, kv
    head and tile at a time: the tile of ``S`` is read once (not at all for
    a row from zeros, whatever its slot holds), asked for ``phi(Q)^T S`` of
    the row's tokens while in VMEM, scaled by the row's whole decay, given
    ``phi(K)^T (decay * V)``, and written back in place.  ``phi`` is built a
    tile and 128 tokens at a time in VMEM, transposed (features down the
    sublanes, tokens across the lanes): an 8-row slab is 8 channels of ``u``
    times one channel.  Token blocks outside a row are not visited; tokens
    of a block that are another row's are selected out.

    Returns ``(num [KVH, NB, G, 128, d] f32, s_pool)``: ``phi(q_t)^T S_0``
    for every token of a row that continues from a state ``S_0`` (zeros
    elsewhere), and the pool with the live rows' states advanced.

    Grid ``(kv heads, rows, tiles)``, sequential.  Visits past the live rows
    repeat the last live block (nothing is fetched or written for them);
    visits of rows from zeros name, as their INPUT, the last block a row
    with history read (nothing is fetched); with no live row at all the one
    block they all name is copied through unchanged."""
    KVH, NB, G, d, _ = q.shape
    L, N, _, F, _ = s_pool.shape
    R = slot.shape[0]
    assert F == held_rows(d) and q.shape[-1] == TOKENS
    if not interpret:
        check_retention_geometry(KVH * G, KVH, d)
    tiles, rows = d // (2 * BLOCK), tile_rows(d)

    def state_in(j, r, p, layer, slot, t0, qlen, n_hist, n_live):
        some = n_hist[0] > 0
        past = r >= n_hist[0]
        row = slot[jnp.clip(jnp.minimum(r, n_hist[0] - 1), 0, R - 1)]
        return (layer[0], jnp.where(some, row, 0), jnp.where(some, j, 0),
                jnp.where(some, jnp.where(past, tiles - 1, p), 0), 0)

    def state_out(j, r, p, layer, slot, t0, qlen, n_hist, n_live):
        some = n_live[0] > 0
        past = r >= n_live[0]
        row = slot[jnp.clip(jnp.minimum(r, n_live[0] - 1), 0, R - 1)]
        return (layer[0], jnp.where(some, row, 0), jnp.where(some, j, 0),
                jnp.where(some, jnp.where(past, tiles - 1, p), 0), 0)

    def decay_map(j, r, p, layer, slot, t0, qlen, n_hist, n_live):
        return jnp.clip(jnp.minimum(r, n_live[0] - 1), 0, R - 1), j, 0, 0

    head = lambda *shape: pl.BlockSpec(
        (None,) + shape, lambda j, r, p, *pre: (j,) + (0,) * len(shape))
    state = lambda index: pl.BlockSpec((None, None, None, rows, d), index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(KVH, R, tiles),
        in_specs=[head(NB, G, d, TOKENS), head(NB, d, TOKENS),
                  head(NB, TOKENS, d),
                  pl.BlockSpec((None, None, 1, d), decay_map),
                  state(state_in)],
        out_specs=[head(NB, G, TOKENS, d), state(state_out)],
        scratch_shapes=[
            pltpu.VMEM((rows, G * TOKENS), jnp.float32),     # phi(Q)^T
            pltpu.VMEM((rows, TOKENS), jnp.float32),         # phi(K)^T
        ],
    )
    # both ends of the pipeline twice, the scratch, the product's temporaries
    held = 4 * (2 * (2 * NB * G * d + 2 * NB * d + 2 * rows) * TOKENS
                + 3 * rows * (G + 1) * TOKENS)
    return pl.pallas_call(
        functools.partial(_chunk_kernel, d=d, group=G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((KVH, NB, G, TOKENS, d), jnp.float32),
                   jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype)],
        # operand 10 (after the six prefetched scalars): the pool
        input_output_aliases={10: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=min(max(16 << 20, held + (8 << 20)), 100 << 20),
        ),
        name="retention_chunk_tpu",
    )(
        _as_i32(layer), _as_i32(slot), _as_i32(t0), _as_i32(qlen),
        _as_i32(n_hist), _as_i32(n_live),
        q, k, vo, decay, s_pool,
    )
