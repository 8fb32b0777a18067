"""The gated delta rule (Gated Delta Networks, arXiv:2412.06464): a
linear-attention mixer whose memory is one matrix a value head, whatever the
sequence's length.

A value head keeps ``S [dk, dv]``.  With ``k_t`` L2-normalised, a log decay
``g_t <= 0`` and a write strength ``beta_t`` in (0, 1)::

    S'  = exp(g_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

(``q`` arrives L2-normalised times ``dk ** -0.5``).  The rule overwrites what
the state held under ``k_t`` by ``beta_t`` of the way to ``v_t``: the state
stays of the size of the values however long the context.

Three forms of the same function live here, all ``jax.numpy`` in float32 at
the highest matmul precision: the recurrence one token at a time
(``delta_step``: the decode kernel's oracle and the CPU path), the same
scanned over a sequence (``delta_recurrence``: the definition, what the
tests hold the rest to), and the CHUNKED form, 64 tokens at a time, in two
halves: what does not read the state (``state_free``: the decay mask, the
unit lower triangular system and its inverse, for every chunk at once) and
the three products a chunk that do (``against_state``), in order.  A
sequence from a given state runs it (``delta_sequence``), and the rows of
fresh tokens of a step that continue from their slots' states
(``delta_rows``).  On a TPU the second half and the decode step are
``ops/deltanet_kernel.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


def l2norm(x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def split_heads(x, nk: int, nv: int, dk: int, dv: int):
    """``x [..., 2 * nk * dk + nv * dv]`` (q | k | v after the convolution
    and its SiLU) -> ``q, k [..., nv, dk]``, ``v [..., nv, dv]`` float32: q
    and k normalised a head, q times ``dk ** -0.5``, each key head repeated
    for the ``nv / nk`` value heads it serves (value head ``j`` reads key
    head ``j // (nv / nk)``)."""
    lead = x.shape[:-1]
    q, k, v = jnp.split(x, [nk * dk, 2 * nk * dk], axis=-1)
    q = l2norm(q.reshape(lead + (nk, dk))) * dk ** -0.5
    k = l2norm(k.reshape(lead + (nk, dk)))
    q, k = (jnp.repeat(a, nv // nk, axis=-2) for a in (q, k))
    return q, k, v.reshape(lead + (nv, dv)).astype(jnp.float32)


def delta_step(q, k, v, g, beta, S):
    """The recurrence, one token a row: ``q, k [B, H, dk]``, ``v [B, H,
    dv]``, ``g, beta [B, H]``, ``S [B, H, dk, dv]``.  Returns ``(o [B, H,
    dv], S)``, float32."""
    S = jnp.exp(g)[..., None, None] * S
    held = jnp.einsum("bhk,bhkv->bhv", k, S, precision=_HI)
    S = S + k[..., :, None] * (beta[..., None] * (v - held))[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, S, precision=_HI), S


def delta_recurrence(q, k, v, g, beta, S0):
    """The definition over one sequence, token by token: ``q, k [T, H,
    dk]``, ``v [T, H, dv]``, ``g, beta [T, H]``, from ``S0 [H, dk, dv]``.
    Returns ``(o [T, H, dv], S_T)``."""

    def token(S, x):
        o, S = delta_step(*(a[None] for a in x), S[None])
        return S[0], o[0]

    S, o = jax.lax.scan(token, S0, (q, k, v, g, beta))
    return o, S


def unit_lower_inverse(M):
    """``(I + M)^-1`` for ``M [..., C, C]`` strictly lower triangular, ``C``
    a power of two: the block recursion of a triangular inverse,
    ``[[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]``, from the
    diagonal's ones up, every level of every system at once, in float32,
    and no step that waits on the row before it (forward substitution's
    63).  The systems are the MINOR axis while it runs: the blocks of the
    lower levels are a few numbers wide, which the matrix unit pads to its
    tiles a system at a time, and the vector unit takes across systems."""
    C = M.shape[-1]
    lead = M.shape[:-2]
    M = jnp.moveaxis(M.reshape((-1, C, C)), 0, -1)             # [C, C, n]
    n = M.shape[-1]
    D = jnp.ones((C, 1, 1, n), M.dtype)          # the diagonal blocks' inverses
    s = 1
    while s < C:
        nb = C // (2 * s)
        # B: the lower left s x s of each diagonal block of 2s
        Mb = M.reshape((nb, 2 * s, nb, 2 * s, n))[:, s:, :, :s]
        B = jnp.sum(jnp.where(
            jnp.eye(nb, dtype=bool)[:, None, :, None, None], Mb, 0.0), axis=2)
        D = D.reshape((nb, 2, s, s, n))
        A, Dd = D[:, 0], D[:, 1]                             # [nb, s, s, n]
        BA = jnp.sum(B[:, :, :, None] * A[:, None], axis=2)
        low = -jnp.sum(Dd[:, :, :, None] * BA[:, None], axis=2)
        D = jnp.concatenate([
            jnp.concatenate([A, jnp.zeros_like(A)], axis=2),
            jnp.concatenate([low, Dd], axis=2)], axis=1)
        s *= 2
    return jnp.moveaxis(D[0], -1, 0).reshape(lead + (C, C))


def state_free(q, k, v, g, beta):
    """The half of the chunked form (the WY form of the paper's section 3.3)
    that does not read the state, for ``n`` chunks of ``C`` tokens at once:
    ``q, k [n, C, H, dk]``, ``v [n, C, H, dv]``, ``g, beta [n, C, H]``.  A
    token of zeros with ``beta 0`` and ``g 0`` (padding behind a row's last
    token) writes nothing and decays nothing; a chunk of such tokens leaves
    the state as it is.

    Inside a chunk token ``i``'s write depends on every earlier write: with
    ``M_ij = beta_i (k_i . k_j) exp(G_i - G_j)`` for ``j < i`` (``G`` the
    running sum of ``g``), the writes are ``(I + M)^-1`` applied to ``beta
    v`` less what the old state held under each key.  Returns, a head
    ``[n, H, ...]``: ``sol_v [C, dv]`` and ``sol_k [C, dk]`` (``(I + M)^-1``
    applied to ``beta v`` and to ``beta k e^G``), ``qg = q e^G [C, dk]``,
    ``qk = (q k^T) . decay [C, C]``, ``kd = k e^(last - G) [C, dk]`` and
    ``elast = e^last``: all a chunk needs beside the state it meets."""
    C, dv = q.shape[1], v.shape[-1]
    G = jnp.cumsum(g, axis=1).transpose(0, 2, 1)               # [n, H, C]
    low = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(low, G[..., :, None] - G[..., None, :], -jnp.inf))
    qh, kh, vh = (a.transpose(0, 2, 1, 3) for a in (q, k, v))  # [n, H, C, d]
    bh = beta.transpose(0, 2, 1)[..., None]                    # [n, H, C, 1]
    eG = jnp.exp(G)[..., None]
    kk = jnp.einsum("nhid,nhjd->nhij", kh, kh, precision=_HI)
    M = bh * kk * jnp.where(jnp.eye(C, dtype=bool), 0.0, decay)
    sol = jnp.einsum(
        "nhij,nhjd->nhid", unit_lower_inverse(M),
        jnp.concatenate([bh * vh, bh * kh * eG], axis=-1), precision=_HI)
    qk = jnp.einsum("nhid,nhjd->nhij", qh, kh, precision=_HI) * decay
    last = G[..., -1:]
    return (sol[..., :dv], sol[..., dv:], qh * eG, qk,
            kh * jnp.exp(last - G)[..., None], jnp.exp(last[..., 0]))


def against_state(free, S):
    """One chunk of ``state_free`` against the state it meets, ``S [H, dk,
    dv]``: the three products that read it.  Returns ``(o [H, C, dv], S
    after the chunk)``."""
    sol_v, sol_k, qg, qk, kd, elast = free
    new = sol_v - jnp.einsum(
        "hck,hkv->hcv", sol_k, S, precision=_HI)               # the writes
    o = jnp.einsum("hck,hkv->hcv", qg, S, precision=_HI) + jnp.einsum(
        "hij,hjv->hiv", qk, new, precision=_HI)
    S = elast[:, None, None] * S + jnp.einsum(
        "hck,hcv->hkv", kd, new, precision=_HI)
    return o, S


def delta_sequence(q, k, v, g, beta, S0):
    """``delta_recurrence``'s function in the chunked form: one sequence of
    any length from ``S0``."""
    T = q.shape[0]
    n = -(-T // CHUNK)
    pad = n * CHUNK - T

    def chunks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((n, CHUNK) + a.shape[1:])

    def chunk(S, free):
        o, S = against_state(free, S)
        return S, o

    S, o = jax.lax.scan(
        chunk, S0, state_free(*(chunks(a) for a in (q, k, v, g, beta))))
    return o.transpose(0, 2, 1, 3).reshape((n * CHUNK,) + v.shape[1:])[:T], S


SLAB = 8    # chunks a pass of the two halves: one 512-token row


def chunk_table(t0, qlen, hist, slots, n: int, pool_slots: int,
                chunk: int = CHUNK):
    """The chunks of a segment's rows, in the order they run: chunk ``c`` of
    row ``r`` starts at ``t0[r] + 64 c`` (``chunk``: another block than this
    module's 64, ``ops/ssd.py``'s).  Rows with tokens and a slot come
    first, then rows with tokens and no slot (a row's chunks stay together
    and in order; rows do not depend on each other), then entries past the
    rows' ends, which are inert.  ``n >= sum ceil(qlen / 64)`` entries,
    static.  Returns ``(table, count)``: a dict of ``[n]`` arrays, ``start``,
    ``left`` (the row's tokens from the chunk's first on: the chunk's own
    are the first ``min(left, 64)``; 0 for an inert entry), ``first`` (of
    its row), ``write`` (the last of a row with a slot: its state goes back
    to the pool), ``slot`` (clipped into the pool), ``has_slot``,
    ``from_state`` (the row continues from its slot's state: ``hist > 0``);
    and the entries that are some row's."""
    R = t0.shape[0]
    has_slot = slots < pool_slots
    order = jnp.argsort(
        jnp.where(qlen > 0, jnp.where(has_slot, 0, 1), 2), stable=True)
    t0, qlen, hist, slots, has_slot = (
        a[order] for a in (t0, qlen, hist, slots, has_slot))
    counts = ((qlen + chunk - 1) // chunk).astype(jnp.int32)
    ends = jnp.cumsum(counts)
    e = jnp.arange(n, dtype=jnp.int32)
    r = jnp.minimum(jnp.sum(ends[None, :] <= e[:, None], axis=1), R - 1)
    live = e < ends[-1]
    c = e - (ends[r] - counts[r])
    return {
        "start": t0[r] + c * chunk,
        "left": jnp.where(live, qlen[r] - c * chunk, 0),
        "first": live & (c == 0),
        "write": live & has_slot[r] & (c == counts[r] - 1),
        "slot": jnp.clip(slots[r], 0, pool_slots - 1).astype(jnp.int32),
        "has_slot": live & has_slot[r],
        "from_state": has_slot[r] & (hist[r] > 0),
    }, ends[-1]


def delta_rows(q, k, v, g, beta, t0, qlen, hist, slots, S_pool, layer, *,
               backend=None, interpret: bool = False):
    """Rows of fresh tokens on one flat axis (a prefill segment): row ``r``
    is the ``qlen[r]`` tokens from ``t0[r]`` of the sequence in slot
    ``slots[r]``, with ``hist[r]`` tokens behind it (0: it starts from
    zeros).  A row runs ``ceil(qlen / 64)`` chunks against its slot's state,
    read once and written once; a row with no token is not visited; a row
    whose slot lies past the pool (no slot) starts from zeros and writes
    nothing.  ``q, k [T, H, dk]``, ``v [T, H, dv]``, ``g, beta [T, H]``,
    ``S_pool [L, N, H, dk, dv]``.  Returns ``(o [T, H, dv] float32,
    S_pool)``.

    In two halves, ``SLAB`` chunks of the rows' table a pass and as many
    passes as the rows have chunks for (one, where the table is no longer):
    what does not read the state (``state_free``) for the pass's chunks at
    once, then those chunks in order against the state: on a TPU
    ``deltanet_chunk_tpu``, which keeps ``S`` on the chip from a row's first
    chunk to its last (``interpret``: the same kernel in interpret mode, for
    tests on a CPU with ``backend="pallas"``); on a CPU, or for
    ``backend="reference"``, a scan of ``against_state``."""
    from helix_tpu.ops.attention import resolve_backend

    T, R = q.shape[0], t0.shape[0]
    N = S_pool.shape[1]
    # a row's first chunk may hold one token, every further one holds 64
    n = min(R, T) + (T - min(R, T)) // CHUNK
    m = min(n, SLAB)
    table, count = chunk_table(t0, qlen, hist, slots, -(-n // m) * m, N)
    pallas = resolve_backend(backend) == "pallas"
    if pallas:
        from helix_tpu.ops.deltanet_kernel import deltanet_chunk_tpu
    at = jnp.arange(CHUNK, dtype=jnp.int32)

    def slab(i, carry):
        o, S_pool, S = carry
        tab = {key: jax.lax.dynamic_slice_in_dim(a, i * m, m)
               for key, a in table.items()}
        mine = at < tab["left"][:, None]                       # [m, C]
        where = tab["start"][:, None] + at
        # what lies behind a row's last token in its last chunk is a
        # neighbour's, or padding whose values nothing vouches for (a
        # kernel leaves the rows it skips unwritten: NaN is possible):
        # selected out, never multiplied out
        own = lambda a: jnp.where(
            mine.reshape(mine.shape + (1,) * (a.ndim - 1)),
            a[jnp.clip(where, 0, T - 1)], 0.0)
        free = state_free(own(q), own(k), own(v), own(g), own(beta))
        if pallas:
            oc, S_pool, S = deltanet_chunk_tpu(
                *free, S_pool, S, layer, tab, jnp.clip(count - i * m, 0, m),
                interpret=interpret)
        else:
            def entry(carry, x):
                S, pool = carry
                free, slot, first, from_state, dest = x
                S = jnp.where(first, jnp.where(
                    from_state, pool[layer, slot], 0.0), S)
                oc, S = against_state(free, S)
                return (S, pool.at[layer, dest].set(S, mode="drop")), oc

            (S, S_pool), oc = jax.lax.scan(entry, (S, S_pool), (
                free, tab["slot"], tab["first"], tab["from_state"],
                jnp.where(tab["write"], tab["slot"], N)))
        # [m, H, C, dv] back onto the flat axis
        oc = oc.transpose(0, 2, 1, 3).reshape((m * CHUNK,) + o.shape[1:])
        return o.at[jnp.where(mine, where, T).reshape(-1)].set(
            oc, mode="drop"), S_pool, S

    # what no row owns reads zeros
    carry = (jnp.zeros((T,) + v.shape[1:], jnp.float32), S_pool,
             jnp.zeros(S_pool.shape[2:], S_pool.dtype))
    if n == m:
        o, S_pool, _ = slab(0, carry)
    else:
        o, S_pool, _ = jax.lax.fori_loop(0, (count + m - 1) // m, slab, carry)
    return o, S_pool


def delta_decode(q, k, v, g, beta, S_pool, layer, live, *, backend=None,
                 interpret: bool = False):
    """One decode step of every slot: row ``b`` is slot ``b``'s one fresh
    token (``live [B]`` bool: idle slots and rows that sit the step out
    write nothing and read zeros).  ``q, k [B, H, dk]``, ``v [B, H, dv]``,
    ``g, beta [B, H]``; the pool ``S [L, N, H, dk, dv]`` with ``N >= B``,
    updated IN PLACE at ``layer``.  Returns ``(o [B, H, dv] float32,
    S_pool)``.

    On a TPU it is one pass of ``deltanet_decode_tpu`` over the live slots
    (``interpret``: the same kernel in interpret mode, for tests on a CPU
    with ``backend="pallas"``); on a CPU, or for ``backend="reference"``,
    the plain recurrence."""
    from helix_tpu.ops.attention import resolve_backend

    B = q.shape[0]
    N = S_pool.shape[1]
    if resolve_backend(backend) == "pallas":
        from helix_tpu.ops.deltanet_kernel import deltanet_decode_tpu

        order = jnp.argsort(~live, stable=True).astype(jnp.int32)
        o, S_pool = deltanet_decode_tpu(
            q, k, v, jnp.exp(g), beta, S_pool, layer, order,
            jnp.sum(live).astype(jnp.int32), interpret=interpret)
        return jnp.where(live[:, None, None], o, 0.0), S_pool
    o, S = delta_step(q, k, v, g, beta, S_pool[layer, :B])
    dest = jnp.where(live, jnp.arange(B, dtype=jnp.int32), N)
    S_pool = S_pool.at[layer, dest].set(S, mode="drop")
    return jnp.where(live[:, None, None], o, 0.0), S_pool
