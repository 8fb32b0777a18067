"""Pallas TPU kernel: one decode step of a Mamba-2 layer, the state's decay,
its write and its read-out in ONE pass.

XLA's form of ``ops/ssd.py::ssd_step_packed`` walks the state twice (the
update, then the product with ``C``), and the state is over a third of a
decode step's bytes.  Here a block of the pool's rows (``ops/ssd.py``: ``pack``
heads to a 128-lane tile, the state axis down the sublanes, ``[N, pack * P]``
a row) is read once, decayed by its heads' scalars across the lanes, written
``B (dt x)^T``, read out under ``C`` while it is still in VMEM, and written
back through ``input_output_aliases``: one read and one write of the state a
row, a layer and a step, over the live slots only.

``B`` and ``C`` are needed down the sublanes: one ``[128, 128]`` transpose a
vector and GROUP (the heads of a group share them), as in
``ops/deltanet_kernel.py``, whose frame this kernel runs in
(``state_decode_call``: the grid over rows and head blocks, the live rows
first by scalar prefetch, the pool aliased in and out).  The read-out is a
sublane reduction; everything else is elementwise on ``[128, 128]`` tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from helix_tpu.ops.deltanet_kernel import head_block, state_decode_call
from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry
from helix_tpu.ops.ssd import head_pack, lanes, rows_of

ROW_BLOCK = 16      # packed rows a grid step: 1 MB of state at 128 x 128


def check_ssd_geometry(heads: int, head_dim: int, groups: int,
                       state: int) -> None:
    """Raise :class:`UnsupportedKernelGeometry` for what Mosaic refuses, or
    the kernel does not do: a packed row is a square ``[128, 128]`` tile
    (``B`` and ``C`` go down the sublanes by a square transpose), a row holds
    heads of one group, and the rows come in blocks of 8 (a sublane tile of
    the vectors)."""
    k = head_pack(head_dim)
    why = None
    if k * head_dim != 128 or state != 128:
        why = ("the state size and the packed head width must both be the "
               "128 lanes")
    elif heads % groups or (heads // groups) % k:
        why = "the heads of a group must fill whole packed rows"
    elif head_block(heads // k, ROW_BLOCK) % 8:
        why = "the packed rows must come in blocks of 8 (a sublane tile)"
    if why:
        raise UnsupportedKernelGeometry(
            "ssd decode kernel: no TPU lowering for "
            f"{heads} heads of {head_dim} in {groups} groups over a state "
            f"of {state}: {why}.  Serve this geometry with "
            "attn_backend='reference' explicitly, or extend the kernel.")


def _live(x_ref, a_ref, b_ref, c_ref, s_ref, o_ref, so_ref, *, hb: int,
          per_group: int, d: int):
    def column(row):
        # [j, c] = u[j]: a vector down the sublanes, across every lane
        return jnp.broadcast_to(row, (d, d)).T

    for i in range(hb):                                      # static unroll
        at = pl.ds(i, 1)
        if i % per_group == 0:
            # the rows of one group share B and C
            bc, cc = column(b_ref[at, :]), column(c_ref[at, :])
        s = s_ref[i] * a_ref[at, :] + bc * x_ref[at, :]
        so_ref[i] = s
        o_ref[at, :] = jnp.sum(cc * s, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode_tpu(
    xdt,        # [B, H, P] f32: dt * x
    decay,      # [B, H] f32: exp(dt * A)
    Bm,         # [B, G, N] f32
    Cm,         # [B, G, N] f32
    h_pool,     # [L, slots, H / pack, N, pack * P] f32, slots >= B
    layer,      # which of the L layers (a traced index)
    order,      # [B] int32: the live rows first
    count,      # how many of them are live
    *,
    interpret: bool = False,
):
    """Returns ``(y [B, H, P] f32, h_pool)``: ``h_t C_t`` of every live row
    (rows that are not live hold whatever was there), and the pool with the
    live slots' states advanced one token, in place."""
    B, H, P = xdt.shape
    G, N = Bm.shape[1:]
    I, W = h_pool.shape[2], h_pool.shape[4]
    assert h_pool.shape[2:] == (H * P // W, N, W) and h_pool.shape[1] >= B
    if not interpret:
        check_ssd_geometry(H, P, G, N)
    hb = head_block(I, ROW_BLOCK)
    per_group = I // G
    if hb % per_group and per_group % hb:
        hb = per_group
    f32 = lambda v: v.astype(jnp.float32)
    y, h_pool = state_decode_call(
        functools.partial(_live, hb=hb, per_group=min(per_group, hb), d=N),
        (f32(xdt).reshape(B, I, W), lanes(f32(decay), P, I),
         rows_of(f32(Bm), I), rows_of(f32(Cm), I)),
        h_pool, layer, order, count, hb=hb, name="ssd_decode_tpu",
        interpret=interpret)
    return y.reshape(B, H, P), h_pool
