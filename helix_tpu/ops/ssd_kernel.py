"""Pallas TPU kernels of a Mamba-2 layer: one decode step (the state's decay,
its write and its read-out in ONE pass, or its read-out alone:
``ssd_decode_tpu``), and the half of the chunked form that reads the state
(``ssd_chunk_tpu``, below its own heading at the end).

XLA's form of ``ops/ssd.py::ssd_step_packed`` walks the state twice (the
update, then the product with ``C``), and the state is over a third of a
decode step's bytes.  Here a block of the pool's rows (``ops/ssd.py``: ``pack``
heads to a 128-lane tile, the state axis down the sublanes, ``[N, pack * P]``
a row) is read once, decayed by its heads' scalars across the lanes, written
``B (dt x)^T``, read out under ``C`` while it is still in VMEM, and written
back through ``input_output_aliases``: one read and one write of the state a
row, a layer and a step, over the live slots only.

**A fused window writes once.**  The recurrence is linear with a scalar gate
a head, so the steps of a window of decode steps that are not its last need
``h_0 C`` alone (``ops/ssd.py::ssd_window_step`` adds the window's own tokens
by their scores): with ``commit`` 0 (data, a prefetched scalar: one program)
the kernel streams the same tiles, reduces them under ``C``, and writes
NOTHING: every visit names one output block, which goes back as it came.  The
window's last step commits its ``commit`` tokens at once, ``h = decay h +
sum_m B_m (dt x)_m^T``, and reads out what it wrote; one token is the step
that stands alone.

``B`` and ``C`` are needed down the sublanes: one ``[128, 128]`` transpose a
vector and GROUP (the heads of a group share them), as in
``ops/deltanet_kernel.py``, whose frame this kernel's is (the grid over rows
and head blocks, the live rows first by scalar prefetch, the pool aliased in
and out).  The read-out is a sublane reduction; everything else is
elementwise on ``[128, 128]`` tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from helix_tpu.ops.deltanet_kernel import (
    FIRST, FROM_STATE, OPEN, WRITE, head_block,
)
from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry
from helix_tpu.ops.ssd import head_pack, lanes, rows_of

ROW_BLOCK = 16      # packed rows a grid step: 1 MB of state at 128 x 128
CHUNK = 128         # tokens a block of the chunk kernel: the published block


def check_ssd_geometry(heads: int, head_dim: int, groups: int, state: int,
                       chunk: int = CHUNK) -> None:
    """Raise :class:`UnsupportedKernelGeometry` for what Mosaic refuses, or
    the kernels do not do: a packed row is a square ``[128, 128]`` tile
    (``B`` and ``C`` go down the sublanes by a square transpose), a row holds
    heads of one group, the rows come in blocks of 8 (a sublane tile of
    the vectors), and the chunked form's block is the 128 tokens of a
    ``[128, 128]`` decay."""
    k = head_pack(head_dim)
    why = None
    if chunk != CHUNK:
        why = (f"the chunked form's block must be {CHUNK} tokens "
               f"(mamba_chunk is {chunk})")
    elif k * head_dim != 128 or state != 128:
        why = ("the state size and the packed head width must both be the "
               "128 lanes")
    elif heads % groups or (heads // groups) % k:
        why = "the heads of a group must fill whole packed rows"
    elif head_block(heads // k, ROW_BLOCK) % 8:
        why = "the packed rows must come in blocks of 8 (a sublane tile)"
    if why:
        raise UnsupportedKernelGeometry(
            "ssd kernels: no TPU lowering for "
            f"{heads} heads of {head_dim} in {groups} groups over a state "
            f"of {state}: {why}.  Serve this geometry with "
            "attn_backend='reference' explicitly, or extend the kernel.")


def _decode_kernel(layer_ref, order_ref, count_ref, commit_ref, c_ref, g_ref,
                   x_ref, b_ref, s_ref, o_ref, so_ref, bc_ref, *, hb: int,
                   per_group: int, d: int, terms: int):
    del layer_ref, order_ref                 # read by the index maps
    first = jnp.logical_and(pl.program_id(0) == 0, pl.program_id(1) == 0)
    count, commit = count_ref[0], commit_ref[0]
    live = pl.program_id(0) < count

    def column(row):
        # [j, c] = u[j]: a vector down the sublanes, across every lane
        return jnp.broadcast_to(row, (d, d)).T

    def rows(write: bool):
        """The block's packed rows under ``C``; ``write``: decayed by the
        window's whole gate and given its tokens' outer products first, and
        stored."""
        for i in range(hb):                                  # static unroll
            at = pl.ds(i, 1)
            if i % per_group == 0:
                # the rows of one group share B and C
                group = i // per_group
                cc = column(c_ref[at, :])
                if write and terms == 1:
                    bc = column(b_ref[group])
                elif write:
                    for m in range(terms):
                        @pl.when(m < commit)
                        def _a_token_of_the_window():
                            bc_ref[m] = column(b_ref[group, pl.ds(m, 1), :])
            s = s_ref[i]
            if write:
                s = s * g_ref[at, :]
                if terms == 1:
                    s = s + bc * x_ref[0, at, :]
                else:
                    # the tokens the window HAS, not the most it may hold
                    s = jax.lax.fori_loop(
                        0, commit,
                        lambda m, s: s + bc_ref[m] * x_ref[m, at, :], s)
                so_ref[i] = s
            o_ref[at, :] = jnp.sum(cc * s, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(live, commit > 0))
    def _commits():
        rows(True)

    @pl.when(jnp.logical_and(live, commit == 0))
    def _reads():
        rows(False)

    # every visit of a pass that writes nothing names ONE output block of the
    # state, the first visit's: copied through once, it goes back as it came
    @pl.when(jnp.logical_and(
        jnp.logical_or(count == 0, commit == 0), first))
    def _nothing_written():
        so_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode_tpu(
    xw,         # [B, M, H / pack, pack * P] f32: the window's tokens' dt * x
                # in the pool's packed rows, each times the decay after its
                # token
    decay,      # [B, H] f32: the window's whole decay, exp(sum of dt * A)
    Bm,         # [B, G, M, N] f32: the window's tokens' B
    Cm,         # [B, G, N] f32: the step's C
    h_pool,     # [L, slots, H / pack, N, pack * P] f32, slots >= B
    layer,      # which of the L layers (a traced index)
    order,      # [B] int32: the live rows first
    count,      # how many of them are live
    commit=1,   # (traced) how many of the M tokens the state is written
                # with; 0: the state is read and nothing is written
    *,
    interpret: bool = False,
):
    """Returns ``(y [B, H, P] f32, h_pool)`` for every live row (rows that
    are not live hold whatever was there).  ``commit > 0``: the live slots'
    states advanced by the window's first ``commit`` tokens in place, ``h =
    decay * h + sum_m B_m xw_m^T`` (``M = 1``: one decode step), and ``y = h
    C``.  ``commit == 0``: ``y = h C`` of the state as it stands, the pool
    bit for bit what it was (one block is written back as it came), and
    what only a write needs is fetched once.

    Grid ``(rows, blocks of packed rows)``, sequential; ``layer``, ``order``,
    ``count`` and ``commit`` go in by scalar prefetch (one program whatever
    the window's length).  Visits past the live rows repeat the last live
    block (nothing is fetched or written for them) and are skipped; with no
    live row at all the one block they all name is copied through
    unchanged.  (The frame is ``ops/deltanet_kernel.py::state_decode_call``'s
    with a pass that writes nothing; kept apart: folding them into one
    changes the program two other models' cells run.)"""
    B, M, I, W = xw.shape
    H, (G, N) = decay.shape[1], Cm.shape[1:]
    P = I * W // H
    assert h_pool.shape[2:] == (I, N, W) and h_pool.shape[1] >= B
    assert Bm.shape == (B, G, M, N)
    if not interpret:
        check_ssd_geometry(H, P, G, N)
    hb = head_block(I, ROW_BLOCK)
    per_group = I // G
    if hb % per_group and per_group % hb:
        hb = per_group
    blocks, gb = I // hb, max(hb // per_group, 1)
    f32 = lambda v: v.astype(jnp.float32)

    def visit(n, j, layer, order, count, commit):
        """The (row, block of packed rows) a visit names: its own while the
        row is live, the last live one after."""
        dead = n >= count[0]
        row = order[jnp.clip(jnp.minimum(n, count[0] - 1), 0, B - 1)]
        return row, jnp.where(dead, blocks - 1, j)

    def written(n, j, *pre):
        """... for what only a pass that writes needs: the first visit's
        throughout a pass that does not."""
        return tuple(
            jnp.where(pre[3][0] > 0, here, first)
            for here, first in zip(visit(n, j, *pre), visit(0, 0, *pre)))

    def spec(block, index, at=visit):
        return pl.BlockSpec(block, lambda n, j, *pre: index(
            pre[0][0], *at(n, j, *pre)))

    vec = lambda l, n, j: (n, j, 0)
    state = lambda l, n, j: (l, n, j, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, blocks),
        in_specs=[
            spec((None, hb, N), vec),
            spec((None, hb, W), vec, written),
            spec((None, M, hb, W), lambda l, n, j: (n, 0, j, 0), written),
            spec((None, gb, M, N),
                 lambda l, n, j: (n, j * hb // per_group // gb, 0, 0),
                 written),
            spec((None, None, hb, N, W), state)],
        out_specs=[spec((None, hb, W), vec),
                   spec((None, None, hb, N, W), state, written)],
        # the window's B a token, down the sublanes
        scratch_shapes=[pltpu.VMEM((M, N, W), jnp.float32)],
    )
    as_i32 = lambda a: jnp.asarray(a, jnp.int32).reshape(-1)
    y, h_pool = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb,
                          per_group=min(per_group, hb), d=N, terms=M),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, I, W), jnp.float32),
                   jax.ShapeDtypeStruct(h_pool.shape, h_pool.dtype)],
        # operand 8 (after the four prefetched scalars): the pool
        input_output_aliases={8: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        name="ssd_decode_tpu",
    )(
        as_i32(layer), as_i32(order), as_i32(count), as_i32(commit),
        rows_of(f32(Cm), I), lanes(f32(decay), P, I),
        f32(xw), f32(Bm), h_pool,
    )
    return y.reshape(B, H, P), h_pool


# ---- the chunked form: the half that reads the state -----------------------

CHUNK_ROW_BLOCK = 8     # packed rows a grid step of the chunk kernel


def _chunk_kernel(layer_ref, slot_ref, flag_ref, count_ref, x_ref, cb_ref,
                  c_ref, b_ref, gs_ref, col_ref, last_ref, s_ref, r_ref,
                  o_ref, so_ref, ro_ref, h_scr, *, hb: int, pack: int):
    del layer_ref, slot_ref                  # read by the index maps
    e = pl.program_id(1)
    flags = flag_ref[e]
    live = e < count_ref[0]
    on = lambda flag: flags & flag != 0
    # a row that starts its sequence meets a state of zeros: no read of its
    # slot, and no product against what it holds
    starts = jnp.logical_and(on(FIRST), jnp.logical_not(on(FROM_STATE)))

    @pl.when(e == 0)
    def _the_row_under_way():
        h_scr[...] = r_ref[...]

    @pl.when(on(OPEN))
    def _a_state_block_opens():
        # written back whatever follows: unchanged, unless its row ends here
        so_ref[...] = s_ref[...]

    @pl.when(jnp.logical_and(on(FIRST), on(FROM_STATE)))
    def _a_row_continues_from_its_slot():
        h_scr[...] = s_ref[...]

    @pl.when(starts)
    def _a_row_starts():
        h_scr[...] = jnp.zeros_like(h_scr)

    @pl.when(live)
    def _a_block():
        _block(starts, x_ref, cb_ref, c_ref, b_ref, gs_ref, col_ref,
               last_ref, o_ref, h_scr, hb=hb, pack=pack)

    @pl.when(on(WRITE))
    def _a_row_ends():
        so_ref[...] = h_scr[...]

    @pl.when(e == pl.num_programs(1) - 1)
    def _hand_on():
        ro_ref[...] = h_scr[...]


def _block(starts, x_ref, cb_ref, c_ref, b_ref, gs_ref, col_ref, last_ref,
           o_ref, h_scr, *, hb: int, pack: int):
    C, W = cb_ref.shape[0], h_scr.shape[-1]
    P = W // pack
    dot = functools.partial(
        jax.lax.dot_general, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    nn = (((1,), (0,)), ((), ()))
    tn = (((0,), (0,)), ((), ()))
    low = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))
    head_of = jax.lax.broadcasted_iota(jnp.int32, (C, W), 1) // P
    cols = lambda i: pl.ds(i * W, W)
    # a head's running sums, then its steps, a token down the sublanes; and
    # whether the token is the block's own
    sums_of = lambda h: col_ref[:, pl.ds(h, 1)]
    step_of = lambda h: col_ref[:, pl.ds(hb * pack + h, 1)]
    mine = col_ref[:, pl.ds(2 * hb * pack, 1)] > 0

    def across(of, i):
        """``[C, W]`` from one ``[C, W]`` (or ``[C, 1]``) a head of packed
        row ``i``: each head's own lanes."""
        out = jnp.broadcast_to(of(i * pack), (C, W))
        for k in range(1, pack):
            out = jnp.where(head_of == k, of(i * pack + k), out)
        return out

    @pl.when(starts)
    def _nothing_held():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(jnp.logical_not(starts))
    def _what_the_state_holds():
        for i in range(hb):                                  # static unroll
            o_ref[:, cols(i)] = jnp.exp(across(sums_of, i)) * dot(
                c_ref[...], h_scr[i], nn)

    cb = cb_ref[...]
    for i in range(hb):                                      # static unroll
        # what lies behind a row's last token is selected out, not
        # multiplied out: nothing vouches for its values
        x = jnp.where(mine, x_ref[:, cols(i)], 0.0) * across(step_of, i)

        def within(h):
            # the decay from token s to token t, built here a head: the exp
            # of a DIFFERENCE of running sums under the causal mask
            decay = jnp.exp(jnp.where(
                low, sums_of(h) - gs_ref[pl.ds(h, 1), :], -jnp.inf))
            return dot(cb * decay, x, nn)

        o_ref[:, cols(i)] += across(within, i)
        last = last_ref[pl.ds(i, 1), :]                      # [1, W]
        h_scr[i] = jnp.exp(last) * h_scr[i] + dot(
            b_ref[...], x * jnp.exp(last - across(sums_of, i)), tn)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_tpu(
    x,          # [n, C, H * P] f32           } ops/ssd.py::state_free of the
    mine,       # [n, C] bool: a block's own  } blocks of ``table``, in its
    dt,         # [n, C, H] f32               } order
    cb,         # [n, G, C, C] f32: C B^T
    Bm,         # [n, G, C, N] f32
    Cm,         # [n, G, C, N] f32
    Gs,         # [n, C, H] f32: the running sum of dt * A from a block's start
    h_pool,     # [L, slots, H / pack, N, pack * P] f32
    h_row,      # [H / pack, N, pack * P] f32: the row under way at entry 0
    layer,      # which of the L layers (a traced index)
    table,      # n entries of ops/deltanet.py::chunk_table at a block of C
    count,      # how many of them are some row's: they come first
    *,
    interpret: bool = False,
):
    """The half of the chunked state-space form that reads the state: the
    table's entries in order, a block of ``C = 128`` tokens each, ``h`` in
    VMEM from a row's first block to its last: read from ``h_pool[layer,
    slot]`` at the first block only if the row continues from it (zeros
    without a read for a row that starts there), written at the last, in
    place; a row whose first block lay before this table continues from
    ``h_row``, and the state of a row whose last lies behind it is handed
    on.  At an entry, for each packed row ``i`` of heads (``pack`` heads of
    one group across the 128 lanes): the decay ``L = exp(Gs_t - Gs_s)``
    under the causal mask a head, in VMEM; ``(C B^T * L) (dt x)`` a head;
    ``exp(Gs) * (C h[i])``, skipped with the read for the first block of a
    row that starts its sequence; and ``h[i] = exp(last) h[i] + B^T (dt x
    exp(last - Gs))``.  What is one scalar a token and head (``dt``,
    ``exp(Gs)``, ``exp(last - Gs)``, ``exp(last)``) is spread across its
    head's lanes HERE, from a column of ``dt`` and one of ``Gs`` a head:
    laid out for the lanes in HBM each would be as large as ``x``, and ``x``
    stays the ``[tokens, H * P]`` rows the layer holds, its tokens that are
    not the block's own (``mine``) selected out here too.  Every product
    float32 at the highest precision.

    Returns ``(y [n, C, H * P] f32, h_pool, h_row)``; entries past the rows'
    ends hold whatever was there.

    Grid ``(blocks of packed rows, entries)``, sequential; the table's flags
    and the slot an entry names are ``ops/deltanet_kernel.py::
    deltanet_chunk_tpu``'s, which see: an entry of a row with no slot, and
    entries past the rows' ends, name the state block of the last row before
    them that has one, and a block is copied through as it opens, so one
    whose row does not end here, or that no row owns, goes back as it came.
    (The two frames are alike and kept apart: folding them into one changes
    the program another model's cell runs.)"""
    n, C, H = dt.shape
    G, N = Bm.shape[1], Bm.shape[-1]
    I, W = h_pool.shape[2], h_pool.shape[4]
    pack = H // I
    assert h_pool.shape[2:] == (I, N, W) == h_row.shape
    assert x.shape == (n, C, I * W)
    if not interpret:
        check_ssd_geometry(H, W // pack, G, N, C)
    per_group = I // G
    hb = head_block(per_group, CHUNK_ROW_BLOCK)
    at = jnp.arange(n, dtype=jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    slotted = jnp.sum(table["has_slot"]).astype(jnp.int32)
    named = table["slot"][jnp.minimum(at, jnp.maximum(slotted - 1, 0))]
    flags = (FIRST * table["first"] + WRITE * table["write"]
             + FROM_STATE * table["from_state"]
             + OPEN * ((at == 0) | (table["first"] & table["has_slot"]))
             ).astype(jnp.int32)

    def per_entry(block, index):
        def index_map(j, e, layer, slot, flags, count):
            return (jnp.minimum(e, jnp.maximum(count[0] - 1, 0)),) + index(j)

        return pl.BlockSpec((None,) + block, index_map)

    def state_map(j, e, layer, slot, flags, count):
        return layer[0], slot[e], j, 0, 0

    group = lambda w: per_entry(
        (None, C, w), lambda j: (j * hb // per_group, 0, 0))
    wide = per_entry((C, hb * W), lambda j: (0, j))
    state = pl.BlockSpec((None, None, hb, N, W), state_map)
    row = pl.BlockSpec((hb, N, W), lambda j, e, *pre: (j, 0, 0))
    # a block of packed rows' heads side by side: [n, I / hb, C, hb * pack]
    down = lambda a: a.reshape(n, C, I // hb, hb * pack).transpose(0, 2, 1, 3)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(I // hb, n),
        in_specs=[wide, group(C), group(N), group(N),
                  per_entry((hb * pack, C), lambda j: (j, 0)),
                  per_entry((None, C, 2 * hb * pack + 1),
                            lambda j: (j, 0, 0)),
                  per_entry((hb, W), lambda j: (j, 0)), state, row],
        out_specs=[wide, state, row],
        scratch_shapes=[pltpu.VMEM((hb, N, W), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_chunk_kernel, hb=hb, pack=pack),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, C, I * W), jnp.float32),
                   jax.ShapeDtypeStruct(h_pool.shape, h_pool.dtype),
                   jax.ShapeDtypeStruct(h_row.shape, h_row.dtype)],
        # operand 11 (after the four prefetched scalars): the pool
        input_output_aliases={11: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        name="ssd_chunk_tpu",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), named, flags,
        count.reshape(1),
        x, cb, Cm, Bm,
        # a head's running sums across the lanes (token s); and down the
        # sublanes (token t) beside its steps and the token's ``mine``
        Gs.transpose(0, 2, 1),
        jnp.concatenate([down(Gs), down(dt), jnp.broadcast_to(
            mine.astype(jnp.float32)[:, None, :, None],
            (n, I // hb, C, 1))], axis=-1),
        lanes(Gs[:, -1], W // pack, I), h_pool, h_row,
    )
