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
tests hold the rest to), and the CHUNKED form (``delta_chunk``: 64 tokens
at a time against the state, the WY form of the paper's section 3.3) that a
row of fresh tokens runs as it continues from its slot's state
(``delta_rows``).  The decode step on a TPU is ``ops/deltanet_kernel.py``.
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


def delta_chunk(q, k, v, g, beta, S):
    """``C`` tokens of one sequence against the state it continues from:
    ``q, k [C, H, dk]``, ``v [C, H, dv]``, ``g, beta [C, H]``, ``S [H, dk,
    dv]``.  A token of zeros with ``beta 0`` and ``g 0`` (padding behind
    the row's last token) writes nothing and decays nothing.  Returns ``(o [C, H, dv],
    S after the chunk)``.

    Inside the chunk token ``i``'s write depends on every earlier write:
    with ``M_ij = beta_i (k_i . k_j) exp(G_i - G_j)`` for ``j < i`` (``G``
    the running sum of ``g``), the writes are ``(I + M)^-1`` applied to
    ``beta v`` less what the old state held under each key: one unit lower
    triangular solve a head, then products."""
    C = q.shape[0]
    G = jnp.cumsum(g, axis=0).T                                # [H, C]
    low = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(low, G[:, :, None] - G[:, None, :], -jnp.inf))
    qh, kh, vh = (a.transpose(1, 0, 2) for a in (q, k, v))     # [H, C, d]
    bh = beta.T[..., None]                                     # [H, C, 1]
    kk = jnp.einsum("hid,hjd->hij", kh, kh, precision=_HI)
    M = bh * kk * jnp.where(jnp.eye(C, dtype=bool), 0.0, decay)
    rhs = jnp.concatenate(
        [bh * vh, bh * kh * jnp.exp(G)[..., None]], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        M + jnp.eye(C, dtype=M.dtype), rhs, lower=True, unit_diagonal=True)
    dv = v.shape[-1]
    new = sol[..., :dv] - jnp.einsum(
        "hck,hkv->hcv", sol[..., dv:], S, precision=_HI)       # the writes
    qk = jnp.einsum("hid,hjd->hij", qh, kh, precision=_HI) * decay
    o = jnp.einsum("hck,hkv->hcv", qh * jnp.exp(G)[..., None], S,
                   precision=_HI) + jnp.einsum(
                       "hij,hjv->hiv", qk, new, precision=_HI)
    last = G[:, -1]
    S = jnp.exp(last)[:, None, None] * S + jnp.einsum(
        "hck,hcv->hkv", kh * jnp.exp(last[:, None] - G)[..., None], new,
        precision=_HI)
    return o.transpose(1, 0, 2), S


def delta_sequence(q, k, v, g, beta, S0):
    """``delta_recurrence``'s function in the chunked form: one sequence of
    any length from ``S0``."""
    T = q.shape[0]
    n = -(-T // CHUNK)
    pad = n * CHUNK - T

    def chunks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((n, CHUNK) + a.shape[1:])

    def chunk(S, x):
        o, S = delta_chunk(*x, S)
        return S, o

    S, o = jax.lax.scan(chunk, S0, tuple(chunks(a) for a in (q, k, v, g, beta)))
    return o.reshape((n * CHUNK,) + o.shape[2:])[:T], S


def delta_rows(q, k, v, g, beta, t0, qlen, hist, slots, S_pool, layer):
    """Rows of fresh tokens on one flat axis (a prefill segment): row ``r``
    is the ``qlen[r]`` tokens from ``t0[r]`` of the sequence in slot
    ``slots[r]``, with ``hist[r]`` tokens behind it (0: it starts from
    zeros).  Live rows come first (``PrefillPlan``'s order).  A row runs
    ``ceil(qlen / 64)`` chunks, one after the other, against its slot's
    state, read once and written once; a row with no token is not visited; a
    row whose slot lies past the pool (no slot) writes back what it read.
    ``q, k [T, H, dk]``, ``v [T, H, dv]``, ``g, beta [T, H]``, ``S_pool [L,
    N, H, dk, dv]``.  Returns ``(o [T, H, dv] float32, S_pool)``."""
    T = q.shape[0]
    N = S_pool.shape[1]
    n_rows = jnp.sum(qlen > 0).astype(jnp.int32)
    # a chunk is cut at a dynamic offset: room behind the axis for the last
    pad = lambda a: jnp.pad(a, ((0, CHUNK),) + ((0, 0),) * (a.ndim - 1))
    q, k, v, g, beta = (pad(a) for a in (q, k, v, g, beta))
    at = jnp.arange(CHUNK, dtype=jnp.int32)

    def row(r, carry):
        o, S_pool = carry
        slot = jnp.clip(slots[r], 0, N - 1)
        S_old = S_pool[layer, slot]

        def chunk(c, cc):
            o, S = cc
            start = t0[r] + c * CHUNK
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, CHUNK, 0)
            mine = (c * CHUNK + at < qlen[r])
            # what lies behind the row's last token in its last chunk is a
            # neighbour's, or padding whose values nothing vouches for (a
            # kernel leaves the rows it skips unwritten: NaN is possible):
            # selected out, never multiplied out
            own = lambda a: jnp.where(
                mine.reshape((CHUNK,) + (1,) * (a.ndim - 1)), cut(a), 0.0)
            oc, S = delta_chunk(own(q), own(k), own(v), own(g), own(beta), S)
            oc = jnp.where(mine[:, None, None], oc, cut(o))
            return jax.lax.dynamic_update_slice_in_dim(o, oc, start, 0), S

        o, S = jax.lax.fori_loop(
            0, (qlen[r] + CHUNK - 1) // CHUNK, chunk,
            (o, jnp.where(hist[r] > 0, S_old, 0.0)))
        return o, S_pool.at[layer, slot].set(
            jnp.where(slots[r] < N, S, S_old))

    o0 = jnp.zeros((T + CHUNK,) + v.shape[1:], jnp.float32)
    o, S_pool = jax.lax.fori_loop(0, n_rows, row, (o0, S_pool))
    return o[:T], S_pool


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
