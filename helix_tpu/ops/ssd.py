"""The selective state space of Mamba-2 (state space duality,
arXiv:2405.21060): a mixer whose memory is one array a head, whatever the
sequence's length.

A head of ``P`` channels keeps ``h [P, N]``.  With a step ``dt_t > 0``, a log
decay ``la_t = dt_t * A <= 0`` (one scalar a head and token), and ``B_t, C_t
[N]`` shared by the heads of a group::

    h_t = exp(la_t) h_{t-1} + (dt_t x_t) B_t^T
    y_t = h_t C_t

(the skip ``D x_t`` and everything around it are the layer's:
``models/llama.py``).  Two forms of the same function live here, ``jax.numpy``
in float32 at the highest matmul precision: the recurrence (``ssd_step`` one
token a row, ``ssd_recurrence`` scanned over a sequence: the definition, what
the tests hold the rest to) and the CHUNKED form at a block of ``chunk``
tokens (``ssd_chunk``: within a block ``Y = ((C B^T) * L) (dt x) + exp(G) C
h_0`` and ``h_end = exp(G_last) h_0 + sum_s exp(G_last - G_s) (dt_s x_s)
B_s^T``, ``G`` the running sum of ``la`` from the block's start), which the
rows of fresh tokens of a step run from their slots' states (``ssd_rows``),
reading a state once and writing it once a row.  On a CPU that is a loop of
``ssd_chunk`` over the rows' blocks.  On a TPU both forms are kernels of
``ops/ssd_kernel.py``: the decode step in one pass over the live slots (a
fused window of them reads the state at every step and writes it at its
last: ``ssd_window_step``), and the chunked form in TWO HALVES, as the delta
rule's and power retention's are: what does not read the state for a pass's
blocks at once in
``jax.numpy`` (``state_free``), then the blocks in order in
``ssd_chunk_tpu``, which builds each head's decay in VMEM and keeps ``h``
there from a row's first block to its last.

THE POOL'S LAYOUT.  A state of head width under 128 is stored with ``pack =
128 / P`` heads to a 128-lane tile and the state axis down the sublanes:
``[H / pack, N, pack * P]`` (``pack_state``; the same bytes as ``[H, P,
N]``).  The decode kernel then decays, writes and reads a tile with ``B`` and
``C`` down the sublanes and everything a head has across the lanes, and a
layer's ``x`` and ``y`` ``[H * P]`` are rows of it as they stand.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def head_pack(P: int) -> int:
    """Heads that share a 128-lane tile of the pool."""
    return 128 // P if P < 128 and 128 % P == 0 else 1


def pack_state(h):
    """``h [..., H, P, N]`` as the pool holds it, ``[..., H / pack, N, pack *
    P]``."""
    *lead, H, P, N = h.shape
    k = head_pack(P)
    h = h.reshape(*lead, H // k, k, P, N)
    return jnp.moveaxis(h, -1, -3).reshape(*lead, H // k, N, k * P)


def unpack_state(hp, P: int):
    """``pack_state``'s inverse."""
    *lead, I, N, W = hp.shape
    k = W // P
    h = jnp.moveaxis(hp.reshape(*lead, I, N, k, P), -3, -1)
    return h.reshape(*lead, I * k, P, N)


def rows_of(v, I: int):
    """``v [..., G, N]`` (a group's ``B`` or ``C``) for each of the ``I``
    packed rows of heads: row ``i`` holds heads of ONE group."""
    return jnp.repeat(v, I // v.shape[-2], axis=-2)


def lanes(v, P: int, I: int):
    """``v [..., H]`` (a scalar a head) across its head's ``P`` lanes of the
    packed rows: ``[..., I, pack * P]``.  By selects over the lanes, which
    XLA fuses into what consumes them (a repeat and a reshape that merges
    two axes into the lanes it writes out: 16.8 MB twice a decode step over
    a window's tokens, PERF.md section 6, PR 56)."""
    v = v.reshape(v.shape[:-1] + (I, -1))
    head_of = jnp.arange(v.shape[-1] * P) // P
    out = v[..., :1]
    for k in range(1, v.shape[-1]):
        out = jnp.where(head_of == k, v[..., k:k + 1], out)
    return jnp.broadcast_to(out, v.shape[:-1] + head_of.shape)


def ssd_step(x, dt, la, Bm, Cm, h):
    """The recurrence, one token a row: ``x [B, H, P]``, ``dt, la [B, H]``,
    ``Bm, Cm [B, G, N]``, ``h [B, H, P, N]``.  Returns ``(y [B, H, P], h)``,
    float32."""
    H, G = x.shape[1], Bm.shape[1]
    Bh, Ch = (jnp.repeat(a, H // G, axis=1) for a in (Bm, Cm))
    h = jnp.exp(la)[..., None, None] * h + (
        (dt[..., None] * x)[..., :, None] * Bh[..., None, :])
    return jnp.einsum("bhpn,bhn->bhp", h, Ch, precision=_HI), h


def ssd_recurrence(x, dt, la, Bm, Cm, h0):
    """The definition over one sequence, token by token: ``x [T, H, P]``,
    ``dt, la [T, H]``, ``Bm, Cm [T, G, N]``, from ``h0 [H, P, N]``.  Returns
    ``(y [T, H, P], h_T)``."""

    def token(h, a):
        y, h = ssd_step(*(v[None] for v in a), h[None])
        return h[0], y[0]

    h, y = jax.lax.scan(token, h0, (x, dt, la, Bm, Cm))
    return y, h


def ssd_step_packed(x, dt, la, Bm, Cm, hp):
    """``ssd_step`` on states as the pool holds them, ``hp [B, I, N, W]``:
    what a CPU runs for a decode step (the kernel's function, elementwise)."""
    B, H, P = x.shape
    I = hp.shape[1]
    hp = lanes(jnp.exp(la), P, I)[:, :, None, :] * hp + (
        rows_of(Bm, I)[..., None]
        * (dt[..., None] * x).reshape(B, I, 1, -1))
    y = jnp.einsum("binw,bin->biw", hp, rows_of(Cm, I), precision=_HI)
    return y.reshape(B, H, P), hp


def ssd_chunk(x, dt, la, Bm, Cm, hp):
    """One block of one sequence against the state it meets: ``x [C, H,
    P]``, ``dt, la [C, H]``, ``Bm, Cm [C, G, N]``, ``hp [I, N, W]`` (packed).
    A token of zeros with ``dt 0`` and ``la 0`` (padding behind a row's last
    token) writes nothing and decays nothing.  Returns ``(y [C, H, P], hp
    after the block)``; everything that exponentiates is float32."""
    C, H, P = x.shape
    G, I = Bm.shape[1], hp.shape[0]
    Gs = jnp.cumsum(la, axis=0)                                  # [C, H]
    xdt = dt[..., None] * x
    # within the block: (C B^T) * L, a group's product under a head's decay
    cb = jnp.einsum("tgn,sgn->gts", Cm, Bm, precision=_HI)
    low = jnp.tril(jnp.ones((C, C), bool))
    L = jnp.exp(jnp.where(low, Gs.T[:, :, None] - Gs.T[:, None, :], -jnp.inf))
    y = jnp.einsum("hts,shp->thp", jnp.repeat(cb, H // G, axis=0) * L, xdt,
                   precision=_HI)
    # against the state, as the pool holds it
    y = y + (lanes(jnp.exp(Gs), P, I) * jnp.einsum(
        "tin,inw->tiw", rows_of(Cm, I), hp, precision=_HI)
             ).reshape(C, H, P)
    last = Gs[-1]
    xw = (xdt * jnp.exp(last - Gs)[..., None]).reshape(C, I, -1)
    hp = lanes(jnp.exp(last), P, I)[:, None, :] * hp + jnp.einsum(
        "sin,siw->inw", rows_of(Bm, I), xw, precision=_HI)
    return y, hp


def state_free(x, dt, la, Bm, Cm, start, left, chunk: int = 128):
    """The half of the chunked form that does not read the state, for the
    ``m`` blocks of a pass at once: block ``e`` is the ``min(left[e],
    chunk)`` tokens from ``start[e]`` of the flat axis (``x [T, H * P]``, a
    token's heads side by side as the layer holds them, ``dt, la [T, H]``,
    ``Bm, Cm [T, G, N]``; ``ops/deltanet.py::chunk_table``).  What lies
    behind a row's last token in its last block is a neighbour's, or padding
    whose values nothing vouches for (a kernel leaves the rows it skips
    unwritten: NaN is possible): selected out, never multiplied out, so it
    reads as a token of zeros with ``dt 0`` and ``la 0``.  Returns the
    blocks' ``x [m, C, H * P]`` AS GATHERED with ``mine [m, C]`` (the kernel
    selects: one pass over ``x`` less), their own ``dt [m, C, H]``, a
    GROUP's ``C B^T [m, G, C, C]`` (the heads of a group share it: their
    decays are the kernel's), ``B`` and ``C`` a group ``[m, G, C, N]`` and
    the running sum ``Gs [m, C, H]`` of ``la`` from each block's start, all a
    block needs beside the state it meets (``ops/ssd_kernel.py::
    ssd_chunk_tpu``, which spreads ``dt`` and what it exponentiates of ``Gs``
    across a head's lanes itself)."""
    T = x.shape[0]
    at = jnp.arange(chunk, dtype=jnp.int32)
    mine = at < left[:, None]                                  # [m, C]
    where = jnp.clip(start[:, None] + at, 0, T - 1)
    own = lambda a: jnp.where(
        mine.reshape(mine.shape + (1,) * (a.ndim - 1)), a[where], 0.0)
    dt, la, Bm, Cm = (own(a) for a in (dt, la, Bm, Cm))
    Bg, Cg = (a.transpose(0, 2, 1, 3) for a in (Bm, Cm))
    cb = jnp.einsum("mgtn,mgsn->mgts", Cg, Bg, precision=_HI)
    return x[where], mine, dt, cb, Bg, Cg, jnp.cumsum(la, axis=1)


def ssd_sequence(x, dt, la, Bm, Cm, h0, chunk: int = 128):
    """``ssd_recurrence``'s function in the chunked form: one sequence of any
    length from ``h0 [H, P, N]``."""
    T, P = x.shape[0], x.shape[-1]
    n = -(-T // chunk)

    def chunks(a):
        a = jnp.pad(a, ((0, n * chunk - T),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((n, chunk) + a.shape[1:])

    def block(hp, a):
        y, hp = ssd_chunk(*a, hp)
        return hp, y

    hp, y = jax.lax.scan(
        block, pack_state(h0), tuple(chunks(a) for a in (x, dt, la, Bm, Cm)))
    return y.reshape((n * chunk,) + x.shape[1:])[:T], unpack_state(hp, P)


# blocks a pass of the two halves.  Two, not a 512-token row's four: with 16 MB
# of a pass's x and y beside a layer's activations the TPU's compiler chose to
# compute the layer's in-projection three times over (PERF.md section 6, PR 46:
# +4.2 ms a 512-token program).  NO TEST HOLDS THIS: only the cell's whole
# 22-layer program shows it, as ``.remat`` in its ``compiled.as_text()`` (the
# three-layer cut of tests/test_tpu_compile_steps.py does not); compile that
# program for the described chip before changing the number.
SLAB = 2


def _rows_in_two_halves(x, dt, la, Bm, Cm, rows, n: int, h_pool, layer,
                        chunk: int, interpret: bool):
    """``ssd_rows`` on a TPU: ``rows = (t0, qlen, hist, slots)`` in a table
    of ``n`` blocks or more, ``SLAB`` of them a pass."""
    from helix_tpu.ops.deltanet import chunk_table
    from helix_tpu.ops.ssd_kernel import ssd_chunk_tpu

    T = x.shape[0]
    m = min(n, SLAB)
    table, count = chunk_table(
        *rows, -(-n // m) * m, h_pool.shape[1], chunk)
    # a token's heads side by side, as the layer holds them: [T, H * P]
    flat = x.reshape(T, -1)

    def slab(i, carry):
        y, h_pool, h = carry
        tab = {key: jax.lax.dynamic_slice_in_dim(a, i * m, m)
               for key, a in table.items()}
        free = state_free(
            flat, dt, la, Bm, Cm, tab["start"], tab["left"], chunk)
        mine = free[1]
        yc, h_pool, h = ssd_chunk_tpu(
            *free, h_pool, h, layer, tab, jnp.clip(count - i * m, 0, m),
            interpret=interpret)
        # back onto the flat axis a block at a time, a block's own tokens
        # over what lies there: a block is one window of the axis, and a
        # window moves at the memory's speed where a scatter or a gather of
        # rows pays for every row (PERF.md section 6, PR 46)
        for e in range(m):
            at = tab["start"][e]
            y = jax.lax.dynamic_update_slice_in_dim(y, jnp.where(
                mine[e][:, None], yc[e],
                jax.lax.dynamic_slice_in_dim(y, at, chunk)), at, 0)
        return y, h_pool, h

    # what no row owns reads zeros; a block of room behind the axis, so that
    # a row's last block is a whole window wherever the row ends
    carry = (jnp.zeros((T + chunk, flat.shape[1]), jnp.float32), h_pool,
             jnp.zeros(h_pool.shape[2:], h_pool.dtype))
    if n == m:
        y, h_pool, _ = slab(0, carry)
    else:
        y, h_pool, _ = jax.lax.fori_loop(0, (count + m - 1) // m, slab, carry)
    return y[:T].reshape(x.shape), h_pool


def ssd_rows(x, dt, la, Bm, Cm, t0, qlen, hist, slots, h_pool, layer, *,
             chunk: int = 128, backend=None, interpret: bool = False):
    """Rows of fresh tokens on one flat axis (a prefill segment): row ``r``
    is the ``qlen[r]`` tokens from ``t0[r]`` of the sequence in slot
    ``slots[r]``, with ``hist[r]`` tokens behind it (0: it starts from
    zeros).  A row runs ``ceil(qlen / chunk)`` blocks against its slot's
    state, read once at its first and written once at its last; a row with no
    token is not visited; a row whose slot lies past the pool (no slot)
    starts from zeros and writes nothing.  ``x [T, H, P]``, ``dt, la [T,
    H]``, ``Bm, Cm [T, G, N]``, ``h_pool [L, slots, I, N, W]``.  Returns ``(y
    [T, H, P] float32, h_pool)``.

    On a TPU in two halves, ``SLAB`` blocks of the rows' table a pass and as
    many passes as the rows have blocks for (one, where the table is no
    longer): ``state_free`` for the pass's blocks at once, then those blocks
    in order in ``ssd_chunk_tpu``, which keeps ``h`` on the chip from a row's
    first block to its last (handed from pass to pass where a row has more
    blocks than a pass) and skips the state's read and its product for a
    row that starts its sequence (``interpret``: the same kernel in interpret
    mode, for tests on a CPU with ``backend="pallas"``).  On a CPU, or for
    ``backend="reference"``, a loop of ``ssd_chunk`` over the rows' live
    blocks: the form the tests hold the kernel to."""
    from helix_tpu.ops.attention import resolve_backend
    from helix_tpu.ops.deltanet import chunk_table

    T, R = x.shape[0], t0.shape[0]
    N = h_pool.shape[1]
    # a row's first block may hold one token, every further one a whole block
    n = min(R, T) + (T - min(R, T)) // chunk
    if resolve_backend(backend) == "pallas":
        return _rows_in_two_halves(
            x, dt, la, Bm, Cm, (t0, qlen, hist, slots), n, h_pool, layer,
            chunk, interpret)
    table, count = chunk_table(t0, qlen, hist, slots, n, N, chunk)
    at = jnp.arange(chunk, dtype=jnp.int32)
    # the loop carries the pool with a slot's rows of heads and its state axis
    # as ONE axis: a block's products then cannot lend the WHOLE pool their
    # layout (a 128-token chunk's program transposed 2.7 GB twice a layer:
    # PERF.md section 6, PR 45); a slot's own 4 MB take what layout they like
    slot_shape = h_pool.shape[2:]
    pool = h_pool.reshape(h_pool.shape[:2] + (-1, slot_shape[-1]))

    def entry(e, carry):
        y, hp, pool = carry
        mine = at < table["left"][e]
        where = table["start"][e] + at
        # what lies behind a row's last token in its last block is a
        # neighbour's, or padding whose values nothing vouches for: selected
        # out, never multiplied out
        own = lambda a: jnp.where(
            mine.reshape((chunk,) + (1,) * (a.ndim - 1)),
            a[jnp.clip(where, 0, T - 1)], 0.0)
        slot = table["slot"][e]
        hp = jnp.where(table["first"][e], jnp.where(
            table["from_state"][e], pool[layer, slot].reshape(slot_shape),
            0.0), hp)
        yc, hp = ssd_chunk(*(own(a) for a in (x, dt, la, Bm, Cm)), hp)
        pool = pool.at[layer, jnp.where(table["write"][e], slot, N)].set(
            hp.reshape(pool.shape[2:]), mode="drop")
        return y.at[jnp.where(mine, where, T)].set(yc, mode="drop"), hp, pool

    # what no row owns reads zeros; entries past the rows' ends are not run
    y, _, pool = jax.lax.fori_loop(0, count, entry, (
        jnp.zeros(x.shape, jnp.float32),
        jnp.zeros(slot_shape, h_pool.dtype), pool))
    return y, pool.reshape(h_pool.shape)


def window_zeros(layers: int, slots: int, heads: int, head_dim: int,
                 groups: int, state: int, steps: int) -> tuple:
    """What the steps of one fused window of ``steps`` decode steps hand one
    another beside the pools: the window's tokens a layer and slot, ``(dt * x
    [layers, slots, steps, H / pack, pack * P]`` in the pool's packed rows,
    ``B [layers, slots, G, steps, N], dt * A [layers, slots, steps, H], which
    slots were live at some step [layers, slots])``, float32.  A step reads
    the tokens up to its own only, so what an earlier window left behind
    them is no term."""
    k = head_pack(head_dim)
    f32 = lambda *shape: jnp.zeros((layers, slots) + shape, jnp.float32)
    return (f32(steps, heads // k, k * head_dim), f32(groups, steps, state),
            f32(steps, heads), jnp.zeros((layers, slots), bool))


def ssd_decode(x, dt, la, Bm, Cm, h_pool, layer, live, *, backend=None,
               interpret: bool = False):
    """One decode step of every slot that stands alone (a window of one):
    ``ssd_window_step`` without its pending tokens.  Returns ``(y [B, H, P]
    float32, h_pool)``."""
    return ssd_window_step(
        x, dt, la, Bm, Cm, h_pool, None, layer, live, 0, True,
        backend=backend, interpret=interpret)[:2]


def ssd_window_step(x, dt, la, Bm, Cm, h_pool, pending, layer, live, step,
                    last, *, backend=None, interpret: bool = False):
    """Step ``step`` (from 0) of a fused window of decode steps, for every
    slot: row ``b`` is slot ``b``'s one fresh token (``live [B]`` bool: idle
    slots and rows that sit the step out write nothing and read zeros).  ``x
    [B, H, P]``, ``dt, la [B, H]``, ``Bm, Cm [B, G, N]``; the pool ``h [L,
    slots, I, N, W]`` with ``slots >= B``, updated IN PLACE at ``layer``.
    ``pending``: ``window_zeros`` (its tokens are written in place at
    ``[layer, :, step]``; None, or room for one step: a window of one).
    Returns ``(y [B, H, P] float32, h_pool, pending)``.

    On a TPU the recurrence is linear with a scalar gate a head, so a step
    that is not the window's ``last`` only READS ``h`` (``ssd_decode_tpu``
    with ``commit`` 0: ``h_0 C``, seen through the decay since the window
    began) and adds the window's own tokens by their scores, ``sum_i
    exp(sum_{i<l<=j} la_l) (C_j . B_i) dt_i x_i``, from ``pending``, where
    its own ``dt x, B, la`` join them; the last step writes ``h`` once with
    all the window's outer products and reads out what it wrote, for every
    slot that was live at some step.  Every decay is ONE ``exp`` of summed
    ``la``, never a product of ``exp``s.  ``step`` and ``last`` are data: one
    program whatever the window's length (``interpret``: the same kernel in
    interpret mode, for tests on a CPU with ``backend="pallas"``).  On a CPU,
    or for ``backend="reference"``, the plain recurrence at every step, and
    ``pending`` as it came."""
    from helix_tpu.ops.attention import resolve_backend

    B, H, P = x.shape
    _, N, I, _, W = h_pool.shape
    if resolve_backend(backend) != "pallas":
        y, hp = ssd_step_packed(x, dt, la, Bm, Cm, h_pool[layer, :B])
        dest = jnp.where(live, jnp.arange(B, dtype=jnp.int32), N)
        h_pool = h_pool.at[layer, dest].set(hp, mode="drop")
        return jnp.where(live[:, None, None], y, 0.0), h_pool, pending
    from helix_tpu.ops.ssd_kernel import ssd_decode_tpu

    run = lambda visit, commit, *a: ssd_decode_tpu(
        *a, h_pool, layer, jnp.argsort(~visit, stable=True).astype(jnp.int32),
        jnp.sum(visit).astype(jnp.int32), commit, interpret=interpret)
    xdt = (dt[..., None] * x).reshape(B, I, W)
    if pending is None or pending[0].shape[2] == 1:
        y, h_pool = run(
            live, 1, xdt[:, None], jnp.exp(la), Bm[:, :, None], Cm)
        return jnp.where(live[:, None, None], y, 0.0), h_pool, pending
    # a row that sits the step out is a term of nothing
    own = lambda a: jnp.where(live.reshape((B,) + (1,) * (a.ndim - 1)), a, 0.0)
    xs, Bs, las, seen = pending
    xs = xs.at[layer, :, step].set(own(xdt))
    Bs = Bs.at[layer, :, :, step].set(own(Bm))
    las = las.at[layer, :, step].set(own(la))
    seen = seen.at[layer].set(jnp.where(step == 0, live, seen[layer] | live))
    pending = xs, Bs, las, seen
    xs, Bs = xs[layer], Bs[layer]
    reached = jnp.arange(xs.shape[1]) <= step                # [M]
    lg = jnp.where(reached[:, None], las[layer], 0.0)        # [B, M, H]
    # the decay after each token up to this step, and since the window began
    after = jnp.exp(jnp.cumsum(lg[:, ::-1], axis=1)[:, ::-1] - lg)
    whole = jnp.exp(jnp.sum(lg, axis=1))                     # [B, H]
    # the last step visits every slot the window touched
    y, h_pool = run(
        jnp.where(last, seen[layer], live), jnp.where(last, step + 1, 0),
        xs * lanes(after, P, I), whole, Bs, Cm)
    sc = jnp.where(reached, jnp.einsum(
        "bgn,bgmn->bgm", Cm, Bs, precision=_HI), 0.0)
    # a token's score under its decay, a head: across the head's lanes
    fresh = jnp.sum(xs * lanes(
        jnp.repeat(sc, H // sc.shape[1], axis=1).transpose(0, 2, 1) * after,
        P, I), axis=1).reshape(B, H, P)
    y = jnp.where(last, y, whole[..., None] * y + fresh)
    return jnp.where(live[:, None, None], y, 0.0), h_pool, pending
