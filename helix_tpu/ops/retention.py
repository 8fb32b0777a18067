"""Power retention of degree 2: a linear-attention mixer whose memory is one
matrix a kv head, whatever the sequence's length.

For query head ``h`` of kv group ``j``, gates ``g_t`` in (0, 1)::

    a_ts   = exp(sum_{r=s+1..t} log g_r[j]) * (q_t[h] . k_s[j]) ** 2     s <= t
    y_t[h] = sum_s a_ts v_s[j] / (sum_s a_ts + eps)

(``q`` arrives times ``head_dim ** -0.5``, so the square carries the
``1 / head_dim``).  ``(q . k) ** 2 = phi(q) . phi(k)`` with ``phi(u)`` the
symmetric second power of ``u``, so the same function is a recurrence::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        Z_t = g_t Z_{t-1} + k_t k_t^T
    y_t[h] = phi(q_t[h])^T S_t / (q_t[h]^T Z_t q_t[h] + eps)

``S [D_held, d]`` is the state; the normaliser ``phi(q) . z`` is held as the
full symmetric matrix ``Z [d, d]`` (the same numbers as ``z``: its ``d (d +
1) / 2`` distinct entries are ``z``'s), 1.5% of the state's bytes, because a
``[d, d]`` outer product needs no packing.

**The packing that is held.**  ``phi(u)`` has ``d (d + 1) / 2`` distinct
products.  They are held in 8 x 8 BLOCKS of the symmetric matrix ``u u^T``:
the blocks ``(a, b)`` with ``a <= b``, a diagonal block whole (weight 1:
both ``u_i u_j`` and ``u_j u_i``), an off-diagonal block times ``sqrt 2`` (it
stands for its mirror too).  ``D_held = d * d / 2 + 4 * d`` (8,704 at width
128, against 8,256 packed exactly: 5% more bytes for rows that are whole
sublane tiles).  Block row ``a`` has ``d/8 - a`` blocks, so rows ``p`` and
``d/8 - 1 - p`` together have ``d/8 + 1``: that pair is one TILE of
``(d/8 + 1) * 64`` rows (1,088), ``d/16`` tiles a head, all the same shape:
what the decode kernel's grid walks.  Inside a tile the row index is ``(r,
s, c)``: ``r`` the row of ``u`` in its block (8), ``s`` the block's place in
the tile (``d/8 + 1``), ``c`` the column in the block (8).

Three forms of the same function live here in ``jax.numpy``, float32 at the
highest matmul precision: the quadratic form over whole sequences (a forward
pass with no cache), the recurrence one token at a time (the decode kernel's
oracle and the CPU path), and the CHUNKED form (a row of fresh tokens that
continues from a state: ``retention_chunk``, the chunk kernel's oracle and
the CPU path).  On a TPU the decode step and the half of the chunked form
that touches the state are ``ops/retention_kernel.py``'s two kernels
(``retention_decode``, ``retention_rows``: chosen by the backend the engine
resolves); the half that reads no state is ``rows_state_free`` here.  A
fused window of decode steps reads ``S`` at every step and writes it at its
last (``retention_window_step``): the recurrence is linear, so for token
``i`` of a window that began at ``S_0``, ``phi(q_i)^T S_i = G_{1..i} phi(q_i)^T
S_0 + sum_{j<=i} G_{j+1..i} (q_i . k_j)^2 v_j``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BLOCK = 8
_HI = jax.lax.Precision.HIGHEST
EPS = 1e-6


def held_rows(d: int) -> int:
    """``D_held``: rows of the state a kv head, in the packing above."""
    if d % (2 * BLOCK):
        raise ValueError(
            f"power retention holds phi in 8 x 8 blocks paired into tiles: "
            f"the head width must be a multiple of 16, not {d}")
    return d * d // 2 + 4 * d


def tile_rows(d: int) -> int:
    """Rows of one tile (a pair of block rows)."""
    return (d // BLOCK + 1) * BLOCK * BLOCK


def phi(u):
    """``[..., d] -> [..., D_held]`` float32: the held symmetric second power
    (``phi(q) . phi(k) == (q . k) ** 2``), built from slices and outer
    products alone (no gather)."""
    d = u.shape[-1]
    nb = d // BLOCK
    held_rows(d)
    u = u.astype(jnp.float32)
    lead = u.shape[:-1]
    tiles = []
    for p in range(nb // 2):
        parts = []
        for a in (p, nb - 1 - p):
            lo = BLOCK * a
            w = jnp.concatenate([
                jnp.ones((BLOCK,), jnp.float32),
                jnp.full((d - lo - BLOCK,), math.sqrt(2.0), jnp.float32)])
            parts.append(
                u[..., lo:lo + BLOCK, None] * (u[..., None, lo:] * w))
        tiles.append(jnp.concatenate(parts, axis=-1).reshape(
            lead + (tile_rows(d),)))
    return jnp.concatenate(tiles, axis=-1)


def _grouped(q, kvh):
    """``[..., H, d] -> [..., KVH, G, d]``."""
    *lead, H, d = q.shape
    return q.reshape(*lead, kvh, H // kvh, d)


def retention_quadratic(q, k, v, log_g, eps: float = EPS):
    """The definition over whole sequences: ``q [B, S, H, d]`` (scaled),
    ``k, v [B, S, KVH, d]``, ``log_g [B, S, KVH]``; every row of the batch
    one sequence from its start.  Returns ``y [B, S, H, d]`` float32."""
    B, S, H, d = q.shape
    KVH = k.shape[2]
    qg = _grouped(q.astype(jnp.float32), KVH)
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    G = jnp.cumsum(log_g.astype(jnp.float32), axis=1)          # [B, S, KVH]
    sc = jnp.einsum("btkgd,bskd->bkgts", qg, k, precision=_HI) ** 2
    causal = jnp.tril(jnp.ones((S, S), bool))
    dec = jnp.exp(jnp.where(
        causal, G.transpose(0, 2, 1)[..., :, None]
        - G.transpose(0, 2, 1)[..., None, :], -jnp.inf))       # [B, KVH, t, s]
    a = sc * dec[:, :, None]
    num = jnp.einsum("bkgts,bskd->btkgd", a, v, precision=_HI)
    den = jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)            # [B, t, KVH, G]
    return (num / (den[..., None] + eps)).reshape(B, S, H, d)


def retention_step(q, k, v, log_g, S, Z, eps: float = EPS):
    """The recurrence, one token a row: ``q [B, H, d]`` (scaled), ``k, v
    [B, KVH, d]``, ``log_g [B, KVH]``, ``S [B, KVH, D_held, d]``, ``Z [B,
    KVH, d, d]``.  Returns ``(y [B, H, d], S, Z)``, all float32."""
    B, H, d = q.shape
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    g = jnp.exp(log_g.astype(jnp.float32))[..., None, None]
    S = g * S + phi(k)[..., :, None] * v[..., None, :]
    num = jnp.einsum(
        "bkgf,bkfc->bkgc", phi(_grouped(q, k.shape[1])), S, precision=_HI)
    den, Z = normaliser_step(q, k, log_g, Z)
    return (num / (den[..., None] + eps)).reshape(B, H, d), S, Z


def normaliser_step(q, k, log_g, Z):
    """The normaliser's half of ``retention_step``: ``(den [B, KVH, G], Z)``
    (what runs beside the decode kernel, which owns ``S``)."""
    KVH = k.shape[1]
    qg = _grouped(q.astype(jnp.float32), KVH)
    k = k.astype(jnp.float32)
    Z = (jnp.exp(log_g.astype(jnp.float32))[..., None, None] * Z
         + k[..., :, None] * k[..., None, :])
    return jnp.einsum("bkgi,bkij,bkgj->bkg", qg, Z, qg, precision=_HI), Z


def retention_chunk(q, k, v, log_g, mask, S0, Z0, eps: float = EPS):
    """The chunked form for ONE row of a flat token axis: ``mask [T]`` marks
    the row's (contiguous) tokens, ``S0 [KVH, D_held, d]`` / ``Z0 [KVH, d,
    d]`` the state it continues from (zeros for a row that starts its
    sequence).  ``q [T, H, d]`` (scaled), ``k, v [T, KVH, d]``, ``log_g [T,
    KVH]``.  Inside the chunk the quadratic form with cumulative log-gates;
    the state for everything before it; the new state the decayed old one
    plus the chunk's decayed outer products.  Returns ``(y [T, H, d], zeros
    off the row; S1; Z1)``, float32.

    One kv head at a time (``lax.map``): ``phi(Q)`` is ``[T, G, D_held]``
    float32 a head (89 MB at 512 tokens, 5 heads, width 128) and is never
    held for all heads at once."""
    T, H, d = q.shape
    KVH = k.shape[1]
    qg = _grouped(q.astype(jnp.float32), KVH).transpose(1, 0, 2, 3)
    k = k.astype(jnp.float32).transpose(1, 0, 2)               # [KVH, T, d]
    v = v.astype(jnp.float32).transpose(1, 0, 2)
    lg = jnp.where(mask[:, None], log_g.astype(jnp.float32), 0.0).T
    pair = jnp.tril(jnp.ones((T, T), bool)) & mask[:, None] & mask[None, :]

    def head(x):
        qh, kh, vh, lgh, S, Z = x        # [T, G, d] [T, d] [T, d] [T] ...
        # tokens off the row have log-gate 0: the sum runs from the row's
        # first token, and its last value is the row's whole decay
        Gc = jnp.cumsum(lgh)
        sc = jnp.einsum("tgd,sd->gts", qh, kh, precision=_HI) ** 2
        a = sc * jnp.exp(jnp.where(
            pair, Gc[:, None] - Gc[None, :], -jnp.inf))[None]
        into = jnp.exp(Gc)                                     # [T]
        num = jnp.einsum("gts,sd->tgd", a, vh, precision=_HI) + into[
            :, None, None] * jnp.einsum(
                "tgf,fc->tgc", phi(qh), S, precision=_HI)
        den = jnp.sum(a, axis=-1).T + into[:, None] * jnp.einsum(
            "tgi,ij,tgj->tg", qh, Z, qh, precision=_HI)
        y = jnp.where(mask[:, None, None], num / (den[..., None] + eps), 0.0)
        out = jnp.where(mask, jnp.exp(Gc[-1] - Gc), 0.0)        # [T]
        S1 = jnp.exp(Gc[-1]) * S + jnp.einsum(
            "tf,tc->fc", phi(kh) * out[:, None], vh, precision=_HI)
        Z1 = jnp.exp(Gc[-1]) * Z + jnp.einsum(
            "t,ti,tj->ij", out, kh, kh, precision=_HI)
        return y, S1, Z1

    y, S1, Z1 = jax.lax.map(head, (qg, k, v, lg, S0, Z0))
    return y.transpose(1, 0, 2, 3).reshape(T, H, d), S1, Z1


def window_zeros(slots: int, kv_heads: int, d: int, steps: int) -> tuple:
    """What the steps of one fused window of ``steps`` decode steps hand one
    another beside the pool, empty: the window's tokens a slot and kv head,
    ``(k [slots, KVH, steps, d], v alike, log-gates [slots, KVH, steps],
    which slots were live at some step [slots])``.  A term of zero ``k`` and
    log-gate 0 is no term."""
    vec = jnp.zeros((slots, kv_heads, steps, d), jnp.float32)
    return (vec, vec, jnp.zeros((slots, kv_heads, steps), jnp.float32),
            jnp.zeros((slots,), bool))


def retention_decode(q, k, v, log_g, S_pool, Z_pool, layer, live, *,
                     backend=None, interpret: bool = False,
                     eps: float = EPS):
    """One decode step of every slot that stands alone (a window of one):
    ``retention_window_step`` without its pending tokens.  Returns ``(y [B,
    H, d] float32, S_pool, Z_pool)``."""
    return retention_window_step(
        q, k, v, log_g, S_pool, Z_pool, None, layer, live, 0, True,
        backend=backend, interpret=interpret, eps=eps)[:3]


def retention_window_step(q, k, v, log_g, S_pool, Z_pool, pending, layer,
                          live, step, last, *, backend=None,
                          interpret: bool = False, eps: float = EPS):
    """Step ``step`` (from 0) of a fused window of decode steps, for every
    slot: row ``b`` is slot ``b``'s one fresh token (``live [B]`` bool: idle
    slots and rows that sit the step out write nothing and read zeros).
    ``q [B, H, d]`` (scaled), ``k, v [B, KVH, d]``, ``log_g [B, KVH]``;
    pools ``S [L, N, KVH, D_held, d]`` and ``Z [L, N, KVH, d, d]`` with ``N
    >= B``, updated IN PLACE at ``layer``.  ``pending``: ``window_zeros`` at
    the window's first step, what the step before returned after (None: a
    window of one).  Returns ``(y [B, H, d] float32, S_pool, Z_pool,
    pending)``.

    On a TPU the recurrence is linear, so a step that is not the window's
    ``last`` only READS ``S`` (``retention_decode_tpu`` with ``commit``
    false: ``phi(q)^T S_0``, seen through the gates since the window began)
    and adds the window's own tokens by their scores, ``sum_j G_{j+1..i} (q
    . k_j)^2 v_j``, from ``pending``, where its own ``k, v, log g`` join
    them; the last step writes ``S`` once with all the window's outer
    products and queries what it wrote, for every slot that was live at some
    step, and hands back ``pending`` empty.  ``Z`` (1.5% of the bytes) steps
    every time.  ``step`` and ``last`` are data: one program whatever the
    window's length (``interpret``: the same kernel in interpret mode, for
    tests on a CPU with ``backend="pallas"``).  On a CPU, or for
    ``backend="reference"``, the plain recurrence at every step, and
    ``pending`` as it came."""
    from helix_tpu.ops.attention import resolve_backend

    B, H, d = q.shape
    KVH = k.shape[1]
    N = S_pool.shape[1]
    rows = jnp.arange(B, dtype=jnp.int32)
    dest = jnp.where(live, rows, N)               # past the pool: dropped
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    if resolve_backend(backend) == "pallas":
        from helix_tpu.ops.retention_kernel import retention_decode_tpu

        den, Z = normaliser_step(q, k, log_g, Z_pool[layer, :B])
        Z_pool = Z_pool.at[layer, dest].set(Z, mode="drop")
        # a row that sits the step out is a term of nothing
        here = live[:, None]
        lg = jnp.where(here, log_g.astype(jnp.float32), 0.0)
        k, v = (jnp.where(here[..., None], x, 0.0) for x in (k, v))
        if pending is None:
            ks, vs, lgs, seen = k[:, :, None], v[:, :, None], lg[..., None], live
        else:
            ks, vs, lgs, seen = pending
            ks, vs = ks.at[:, :, step].set(k), vs.at[:, :, step].set(v)
            lgs, seen = lgs.at[:, :, step].set(lg), seen | live
        # the gates after each token (tokens the window has not reached are
        # zeros: no gate), and the window's whole gate
        after = jnp.exp(jnp.cumsum(lgs[..., ::-1], axis=-1)[..., ::-1] - lgs)
        whole = jnp.exp(jnp.sum(lgs, axis=-1))                 # [B, KVH]
        # the last step visits every slot the window touched
        visit = jnp.where(last, seen, live)
        qg = q.reshape(B, KVH, H // KVH, d)
        num, S_pool = retention_decode_tpu(
            qg, ks, vs * after[..., None],
            jnp.broadcast_to(whole[..., None, None], (B, KVH, 1, d)),
            S_pool, layer, jnp.argsort(~visit, stable=True).astype(jnp.int32),
            jnp.sum(visit).astype(jnp.int32), last, interpret=interpret)
        if pending is not None:
            sc = jnp.einsum("bkgd,bkmd->bkgm", qg, ks, precision=_HI) ** 2
            fresh = jnp.einsum(
                "bkgm,bkmd->bkgd", sc * after[:, :, None], vs, precision=_HI)
            num = jnp.where(
                last, num, whole[..., None, None] * num + fresh)
            pending = jax.tree.map(
                lambda a: jnp.where(last, jnp.zeros_like(a), a),
                (ks, vs, lgs, seen))
        y = (num / (den[..., None] + eps)).reshape(B, H, d)
        return (jnp.where(live[:, None, None], y, 0.0), S_pool, Z_pool,
                pending)
    y, S, Z = retention_step(
        q, k, v, log_g, S_pool[layer, :B], Z_pool[layer, :B], eps)
    S_pool = S_pool.at[layer, dest].set(S, mode="drop")
    Z_pool = Z_pool.at[layer, dest].set(Z, mode="drop")
    return jnp.where(live[:, None, None], y, 0.0), S_pool, Z_pool, pending


def retention_rows(q, k, v, log_g, t0, qlen, hist, slots, S_pool, Z_pool,
                   layer, eps: float = EPS, *, backend=None,
                   interpret: bool = False):
    """Rows of fresh tokens on one flat axis (a prefill segment): row ``r``
    is the ``qlen[r]`` tokens from ``t0[r]`` of the sequence in slot
    ``slots[r]``, with ``hist[r]`` tokens behind it (0: it starts from
    zeros, whatever its slot holds).  A row with no token is not visited; a
    row whose slot lies past the pool (no slot) starts from zeros and writes
    nothing.  The pools are read and written a row at a time, in place.
    Returns ``(y [T, H, d] float32, S_pool, Z_pool)``.

    On a TPU the form is in two halves (``_rows_on_the_kernel``: what reads
    no state once for the whole axis, then ``retention_chunk_tpu`` a row;
    ``interpret``: the same kernel in interpret mode, for tests on a CPU
    with ``backend="pallas"``); on a CPU, or for ``backend="reference"``,
    ``retention_chunk`` a row over the whole axis under the row's mask."""
    from helix_tpu.ops.attention import resolve_backend

    if resolve_backend(backend) == "pallas":
        return _rows_on_the_kernel(
            q, k, v, log_g, t0, qlen, hist, slots, S_pool, Z_pool, layer,
            eps, interpret)
    T, H, d = q.shape
    N = S_pool.shape[1]
    at = jnp.arange(T, dtype=jnp.int32)
    n_rows = jnp.sum(qlen > 0).astype(jnp.int32)

    def row(r, carry):
        y, S_pool, Z_pool = carry
        slot = jnp.clip(slots[r], 0, N - 1)
        dest = jnp.where(slots[r] < N, slot, N)   # past the pool: dropped
        mask = (at >= t0[r]) & (at < t0[r] + qlen[r])
        keep = (hist[r] > 0) & (slots[r] < N)
        yr, S1, Z1 = retention_chunk(
            q, k, v, log_g, mask,
            jnp.where(keep, S_pool[layer, slot], 0.0),
            jnp.where(keep, Z_pool[layer, slot], 0.0), eps)
        return (y + yr, S_pool.at[layer, dest].set(S1, mode="drop"),
                Z_pool.at[layer, dest].set(Z1, mode="drop"))

    y0 = jnp.zeros((T, H, d), jnp.float32)
    return jax.lax.fori_loop(0, n_rows, row, (y0, S_pool, Z_pool))


def rows_state_free(q, k, v, log_g, member):
    """What the chunked form does not read a state for, ONCE for a whole
    flat axis: ``member [R, T]`` bool marks each row's tokens (a token is
    one row's at most).  Scores, their decay and the sums run under a
    same-row-and-causal mask, the cumulative log-gates restarted at each
    row's first token.  ``q [T, H, d]`` (scaled), ``k, v [T, KVH, d]``,
    ``log_g [T, KVH]``.  Returns ``(num [T, H, d], den [T, H], into [T,
    KVH], out [T, KVH], whole [R, KVH])``: the inside-the-row numerator and
    normaliser, the decay from a row's start to each token (what the state
    it continues from is seen through), from each token to its row's last
    (what the token's outer product enters the new state with; 0 off the
    rows), and a row's whole decay."""
    T, H, d = q.shape
    KVH = k.shape[1]
    inside = member.astype(jnp.float32)
    owned = jnp.any(member, axis=0)
    same = jnp.einsum("rt,rs->ts", inside, inside) > 0
    pair = same & jnp.tril(jnp.ones((T, T), bool))
    lg = jnp.where(owned[:, None], log_g.astype(jnp.float32), 0.0)
    Gc = jnp.einsum("ts,sk->tk", pair.astype(jnp.float32), lg, precision=_HI)
    whole = jnp.einsum("rt,tk->rk", inside, lg, precision=_HI)
    last = jnp.einsum("rt,rk->tk", inside, whole, precision=_HI)
    out = jnp.where(owned[:, None], jnp.exp(last - Gc), 0.0)

    def head(x):
        qh, kh, vh, Gh = x                       # [T, G, d] [T, d] [T, d] [T]
        sc = jnp.einsum("tgd,sd->gts", qh, kh, precision=_HI) ** 2
        a = sc * jnp.exp(jnp.where(
            pair, Gh[:, None] - Gh[None, :], -jnp.inf))[None]
        return (jnp.einsum("gts,sd->tgd", a, vh, precision=_HI),
                jnp.sum(a, axis=-1).T)

    num, den = jax.lax.map(head, (
        _grouped(q.astype(jnp.float32), KVH).transpose(1, 0, 2, 3),
        k.astype(jnp.float32).transpose(1, 0, 2),
        v.astype(jnp.float32).transpose(1, 0, 2), Gc.T))
    return (num.transpose(1, 0, 2, 3).reshape(T, H, d),
            den.transpose(1, 0, 2).reshape(T, H), jnp.exp(Gc), out,
            jnp.exp(whole))


def _rows_on_the_kernel(q, k, v, log_g, t0, qlen, hist, slots, S_pool,
                        Z_pool, layer, eps, interpret):
    """``retention_rows`` in two halves.  ``rows_state_free`` for the whole
    axis in XLA; then the rows that have a slot, those that continue from a
    state first: ``S`` in ``retention_chunk_tpu`` (read once and written
    once a row, not read for a row from zeros), ``Z`` (1.5% of the state's
    bytes) in a loop of ``[d, d]`` products here."""
    from helix_tpu.ops.retention_kernel import TOKENS, retention_chunk_tpu

    T, H, d = q.shape
    KVH, N, R = k.shape[1], S_pool.shape[1], t0.shape[0]
    G = H // KVH
    at = jnp.arange(T, dtype=jnp.int32)
    member = (at >= t0[:, None]) & (at < (t0 + qlen)[:, None])     # [R, T]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    num, den, into, out, whole = rows_state_free(q, k, v, log_g, member)
    owned = jnp.any(member, axis=0)

    live = (qlen > 0) & (slots < N)
    cont = live & (hist > 0)
    order = jnp.argsort(
        jnp.where(cont, 0, jnp.where(live, 1, 2)), stable=True)
    n_hist, n_live = (jnp.sum(x).astype(jnp.int32) for x in (cont, live))
    slot = jnp.clip(slots, 0, N - 1).astype(jnp.int32)[order]

    # the token axis in blocks of 128, q and k with channels down the
    # sublanes and tokens across the lanes
    NB = -(-T // TOKENS)
    blocks = lambda a: jnp.pad(
        a, ((0, NB * TOKENS - T),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (NB, TOKENS) + a.shape[1:])
    held, S_pool = retention_chunk_tpu(
        blocks(q).reshape(NB, TOKENS, KVH, G, d).transpose(2, 0, 3, 4, 1),
        blocks(k).transpose(2, 0, 3, 1),
        blocks(v * out[..., None]).transpose(2, 0, 1, 3),
        jnp.broadcast_to(whole[order][..., None, None], (R, KVH, 1, d)),
        S_pool, layer, slot, t0[order], qlen[order], n_hist, n_live,
        interpret=interpret)
    held = held.transpose(1, 3, 0, 2, 4).reshape(NB * TOKENS, H, d)[:T]

    qg = _grouped(q, KVH)

    def normaliser(i, carry):
        seen, Z_pool = carry
        r = order[i]
        mine = member[r]
        fresh = jnp.einsum(
            "tk,tki,tkj->kij", jnp.where(mine[:, None], out, 0.0), k, k,
            precision=_HI)

        def continues(seen, Z0):
            return seen + jnp.where(mine[:, None, None], jnp.einsum(
                "tkgi,kij,tkgj->tkg", qg, Z0, qg, precision=_HI), 0.0), (
                    whole[r][:, None, None] * Z0 + fresh)

        # (the slot's matrix is read outside the branch: a branch that
        # closed over the pool would copy it)
        seen, Z1 = jax.lax.cond(
            i < n_hist, continues, lambda seen, Z0: (seen, fresh), seen,
            Z_pool[layer, slot[i]])
        return seen, Z_pool.at[layer, slot[i]].set(Z1)

    seen, Z_pool = jax.lax.fori_loop(
        0, n_live, normaliser,
        (jnp.zeros((T, KVH, G), jnp.float32), Z_pool))
    through = jnp.repeat(into, G, axis=1)                          # [T, H]
    y = (num + through[..., None] * held) / (
        den + through * seen.reshape(T, H) + eps)[..., None]
    return jnp.where(owned[:, None, None], y, 0.0), S_pool, Z_pool
