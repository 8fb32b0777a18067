"""Sparse latent attention behind a learned indexer (DeepSeek Sparse
Attention, ``model_type: glm_moe_dsa``) under the row contract of
``ops/paged.py``.

A token caches TWO rows a layer: its latent row in the latent pool
(``[c | k_pe | zeros]``, ``ops/mla_kernel.py``) and its index key in the
index-key pool beside it (``[L, N, P, Di]``, the same page ids).  A query at
position ``t`` scores every key ``s <= t`` of its own row,

    I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])        (float32),

and attends the ``topk`` keys of largest ``I`` (ties to the smaller ``s``;
all of them while ``t + 1 <= topk``): cached keys and the step's fresh ones
compete together, the query's own position like any other.

Passes over operands that XLA gathers from the pools by the page table
(``pool.reshape(rows)[layer * N + table]``: one gather from the whole pool, no
layer slice is copied):

- **scores** (``index_scores``; on a TPU the kernel ``dsa_index_scores_tpu``):
  a row's queries against its index keys, in blocks over queries and keys; the
  ``[heads, queries, keys]`` product never leaves VMEM, what is written is the
  weighted sum over heads ``[queries, keys]``;
- **choice**: a decode row's ``topk`` positions by ``lax.top_k`` (stable: ties
  to the smaller position).  A chunk's is TWO NUMBERS A QUERY: the ordered
  bits of its ``topk``-th largest score, by bisection over the float's bits
  (32 passes of compare-and-count, no sort), and the position that cuts the
  keys tied AT it (no cut in almost every step).  On a TPU
  ``dsa_threshold_tpu`` finds both over the row's LIVE scores held in VMEM;
  the ``jax.numpy`` path (``topk_mask``: the CPU's, and every test's oracle)
  builds the mask ``[queries, keys]`` itself;
- **attention**: a decode row's H heads over the rows it gathered under a
  bias of its own (``sparse_attention``; ``mla_sparse_attention_tpu``); a
  chunk's query blocks over the row's history and the fresh rows in key
  blocks (``chunk_attention``; the same kernel name's chunk form), each grid
  step making its ``[BQ, BS]`` mask from the scores and the two numbers:
  between the scoring kernel and the attention kernel's output a chunk
  writes to HBM nothing of the page table's width but the float32 scores,
  and touches no key block past the row's history.

A DECODE row (one fresh token: ``max_q_len`` 1) reads its index keys (``Di``
values a token), chooses, and fetches ``topk`` latent rows: never its whole
latent history.  A CHUNK row's queries each keep their own ``topk`` of one
dense copy of the row's history: what a query dropped is masked inside the
attention kernel (per-query sets of 2,048 rows for 512 queries would be 1.3 GB
a layer); the products are those of dense attention, the saving is the decode
row's.  The two differ in kind (a threshold over a dense copy, a count over
gathered rows); what tells them apart is the input's shape.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from helix_tpu.ops.attention import DEFAULT_MASK_VALUE, resolve_backend
from helix_tpu.ops.paged import (
    _row_of_tokens,
    dsa_index_scores_reference,
    mla_sparse_attention_reference,
)


# A verification hook: a callable that, WHILE SET WHEN A PROGRAM IS TRACED,
# receives every call's scores and choice through ``jax.debug.callback``
# (``tests/test_mla_dsa_moe.py`` and ``chip_smoke_deepseek.py`` compare them
# with the plain reference's).  ``PROBE("decode", layer, first page of each row
# [B], hist, q_len, scores [B, S], chosen positions [B, K], kept [B, K])`` or
# ``PROBE("chunk", layer, first pages [R], t0, q_len, hist, scores [T, S + T],
# chosen [T, S + T])``, the key axis a
# row's history positions then the flat axis' tokens (on a TPU both are
# rebuilt for the callback from the kernels' outputs: a served program builds
# neither).  None: nothing is traced
PROBE = None


def _probe(kind, *arrays):
    if PROBE is not None:
        jax.debug.callback(
            lambda *a: PROBE(kind, *(jax.device_get(x) for x in a)), *arrays)


def index_queries(qi, heads: int):
    """``qi [..., Hi * Di + Hi]`` (``models/llama.py::_dsa_index``) as the
    queries ``[..., Hi, Di]`` and the weights ``[..., Hi]``."""
    q, w = qi[..., :-heads], qi[..., -heads:]
    return q.reshape(q.shape[:-1] + (heads, q.shape[-1] // heads)), w


def index_scores(q, w, keys, backend=None, lim=None):
    """``[R, T, S]`` float32 index scores (``dsa_index_scores_reference``'s
    contract): the Pallas kernel on a TPU, ``jax.numpy`` elsewhere.  ``lim
    [R]``: a row's keys past its first ``lim`` are seen by no query: the
    kernel skips their blocks and leaves their scores unspecified."""
    if resolve_backend(backend) == "pallas":
        from helix_tpu.ops.dsa_kernel import dsa_index_scores_tpu

        return dsa_index_scores_tpu(q, w, keys, lim)
    return dsa_index_scores_reference(q, w, keys)


def sparse_attention(q, kv, bias, latent: int, backend=None):
    """``[R, T, H, latent]`` (``mla_sparse_attention_reference``'s
    contract): a decode row over the rows it gathered."""
    if resolve_backend(backend) == "pallas":
        from helix_tpu.ops.dsa_kernel import mla_sparse_attention_tpu

        return mla_sparse_attention_tpu(q, kv, bias, latent=latent)
    return mla_sparse_attention_reference(q, kv, bias, latent)


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def topk_mask(scores, valid, k: int):
    """For each row of ``scores [..., S]``: True at the ``k`` valid entries
    of largest score, ties to the smaller index; at every valid entry of a
    row with no more than ``k``.  The ``k``-th largest value is found by
    bisection over the float's ordered bits: 32 passes of compare-and-count,
    never a sort."""
    key = jnp.where(valid, jnp.maximum(_ordered_bits(scores), 1), 0)

    def bit(i, v):
        cand = v | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        n = jnp.sum(key >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, v)

    thr = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32))
    thr = jnp.maximum(thr, 1)[..., None]        # 0: fewer than k are valid
    at_least = key >= thr

    def cut_ties(_):
        above = key > thr
        need = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
        tie = key == thr
        return above | (tie & (jnp.cumsum(tie, axis=-1) <= need))

    over = jnp.any(jnp.sum(at_least, axis=-1, dtype=jnp.int32) > k)
    return jax.lax.cond(over, cut_ties, lambda _: at_least, None)


def kept(scores, valid, thr, tie):
    """``topk_mask``'s mask again from the two numbers a query that
    ``dsa_kernel.dsa_threshold_tpu`` writes (``thr``, ``tie [...]``): a valid
    entry whose ordered bits are over ``thr``, or AT it at an index no
    larger than ``tie``."""
    bits = jnp.maximum(_ordered_bits(scores), 1)
    thr, tie = thr[..., None], tie[..., None]
    return valid & ((bits > thr) | (
        (bits == thr) & (jnp.arange(scores.shape[-1]) <= tie)))


def _gather_rows(pool, layer, tables):
    """``pool [L, N, P, W]`` -> each row's pages side by side ``[R, maxP *
    P, W]``, one gather from the whole pool."""
    L, N, P, W = pool.shape
    pages = pool.reshape(L * N, P, W)[layer * N + tables]
    return pages.reshape(tables.shape[0], tables.shape[1] * P, W)


def _latent_rows(c_new, r_new, width: int, dtype):
    """Fresh ``[c | k_pe]`` in the latent pool's row layout."""
    row = jnp.concatenate([c_new, r_new], axis=-1).astype(dtype)
    return jnp.pad(row, ((0, 0), (0, width - row.shape[-1])))


def dsa_ragged_paged_attention(
    q,            # [T, H, R + dr] absorbed | rope queries, flat over rows
    c_new,        # [T, R] fresh normed latents
    r_new,        # [T, dr + Di] fresh rope keys | fresh index keys
    qi,           # [T, Hi * Di + Hi] index queries | head weights
    kv_pages,     # [L, N, P, R + 128] latent pool
    idx_pages,    # [L, N, P, Di] index-key pool (the same page ids)
    layer, t0, q_len, hist, tables,
    *,
    index_heads: int,
    topk: int,
    backend: Optional[str] = None,
    max_q_len: Optional[int] = None,
):
    """Ragged paged latent attention in which every query attends the
    ``topk`` keys of its row that its index scores rank first (module
    docstring).  Returns the attended latents ``[T, H, R]``; tokens outside
    every row get zeros."""
    T, H, Dq = q.shape
    L, N, P, W = kv_pages.shape
    Di = idx_pages.shape[-1]
    lat = c_new.shape[-1]
    dt = kv_pages.dtype
    r_new, i_new = r_new[..., :-Di], r_new[..., -Di:]
    fresh = _latent_rows(c_new, r_new, W, dt)                     # [T, W]
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, W - Dq))).astype(dt)
    q_idx, w_idx = index_queries(qi, index_heads)
    n_rows, maxP = tables.shape
    S = maxP * P
    tables = tables.astype(jnp.int32)
    hist = hist.astype(jnp.int32)
    if max_q_len == 1 and T == n_rows:
        # ---- decode rows: token b is row b ------------------------------
        pos = jnp.arange(S, dtype=jnp.int32)
        live = q_len > 0
        with jax.named_scope("attn.index.score"):
            keys = _gather_rows(idx_pages, layer, tables)         # [B, S, Di]
            sc = index_scores(q_idx[:, None], w_idx[:, None], keys,
                              backend, hist * live)[:, 0]         # [B, S]
            own = dsa_index_scores_reference(
                q_idx[:, None], w_idx[:, None], i_new[:, None])[:, 0, 0]
            sc = jnp.where(pos[None] < hist[:, None], sc, -jnp.inf)
            sc = jnp.where((pos[None] == hist[:, None]) & live[:, None],
                           own[:, None], sc)
        with jax.named_scope("attn.index.select"):
            K = min(topk, S)
            _, sel = jax.lax.top_k(sc, K)                         # [B, K]
            n_keys = jnp.where(live, jnp.minimum(hist + 1, K), 0)
            keep = jnp.arange(K)[None] < n_keys[:, None]
            _probe("decode", layer, tables[:, 0], hist, q_len, sc, sel, keep)
        with jax.named_scope("attn.sparse.gather"):
            # the chosen tokens' latent rows, K a row: never the history
            page = jnp.take_along_axis(tables, sel // P, axis=1)
            rows = jnp.where(keep, layer * (N * P) + page * P + sel % P, 0)
            kv = kv_pages.reshape(L * N * P, W)[rows]             # [B, K, W]
            kv = jnp.where((sel == hist[:, None])[..., None],
                           fresh[:, None], kv)
        bias = jnp.where(keep, 0.0, DEFAULT_MASK_VALUE)[:, None]  # [B, 1, K]
        out = sparse_attention(qp[:, None], kv, bias, lat, backend)
        return out[:, 0].astype(q.dtype)
    # ---- rows of several fresh tokens (chunks, packed prompts) -----------
    lim = hist * (q_len > 0)
    with jax.named_scope("attn.index.score"):
        keys = _gather_rows(idx_pages, layer, tables)             # [R, S, Di]
        sc_h = index_scores(q_idx[None], w_idx[None], keys, backend, lim)
        sc_f = index_scores(q_idx[None], w_idx[None], i_new[None],
                            backend)[0]                           # [T, T]
    with jax.named_scope("attn.sparse.gather"):
        kv_h = _gather_rows(kv_pages, layer, tables)              # [R, S, W]
    return chunk_attention(
        qp, kv_h, fresh, sc_h, sc_f, t0, q_len, lim, topk=topk, latent=lat,
        backend=backend, probe=(layer, tables[:, 0], hist)).astype(q.dtype)


def _chunk_dense(sc_h, sc_f, t0, q_len, lim):
    """A chunk's scores as ONE array a query (the ``jax.numpy`` path, and
    what ``PROBE`` is shown): ``scores [T, S + T]``, a token's own row's
    history positions then the flat axis' tokens; ``valid``, the keys a
    query counts (its row's first ``lim`` cached ones, its row's fresh ones
    up to itself); ``onehot [R, T]``, a token's row."""
    R, T, S = sc_h.shape
    row, _ = _row_of_tokens(t0, q_len, T)
    own = jnp.clip(row, 0)[None, :, None]
    onehot = row[None, :] == jnp.arange(R)[:, None]
    ok_h = onehot[:, :, None] & (
        jnp.arange(S, dtype=jnp.int32)[None, None] < lim[:, None, None])
    tok = jnp.arange(T)
    ok_f = ((row >= 0)[:, None] & (row[:, None] == row[None, :])
            & (tok[None, :] <= tok[:, None]))
    scores = jnp.concatenate(
        [jnp.take_along_axis(sc_h, own, axis=0)[0], sc_f], axis=-1)
    valid = jnp.concatenate(
        [jnp.take_along_axis(ok_h, own, axis=0)[0], ok_f], axis=-1)
    return scores, valid, onehot


def chunk_attention(q, kv_h, kv_f, sc_h, sc_f, t0, q_len, lim, *, topk: int,
                    latent: int, backend=None, probe=None):
    """Rows of several fresh tokens on one flat axis: ``q [T, H, W]``, row
    ``r`` the tokens ``[t0[r], t0[r] + q_len[r])`` behind ``lim[r]`` cached
    keys ``kv_h [R, S, W]``, the fresh rows ``kv_f [T, W]``; ``sc_h [R, T,
    S]`` / ``sc_f [T, T]`` every query's index scores of both.  Each query
    attends the ``topk`` keys of its row (cached and fresh together, up to
    itself) that ``topk_mask`` would choose; ``[T, H, latent]``, zeros for a
    token outside every row.

    On a TPU the choice never leaves the kernels as a mask: ``dsa_threshold_
    tpu`` reads the live scores once and writes two numbers a query, and the
    attention kernel makes each block's mask from the same scores."""
    T = q.shape[0]
    if resolve_backend(backend) == "pallas":
        from helix_tpu.ops.dsa_kernel import (
            dsa_threshold_tpu, mla_sparse_chunk_attention_tpu,
        )

        with jax.named_scope("attn.index.select"):
            thr, tie = dsa_threshold_tpu(sc_h, sc_f, t0, q_len, lim,
                                         topk=topk)               # [R, T]
        if PROBE is not None and probe is not None:
            # (what the probe is shown is rebuilt from the kernels' outputs)
            scores, valid, _ = _chunk_dense(sc_h, sc_f, t0, q_len, lim)
            at = (jnp.clip(_row_of_tokens(t0, q_len, T)[0], 0), jnp.arange(T))
            _probe("chunk", *probe[:2], t0, q_len, probe[2], scores,
                   kept(scores, valid, thr[at], tie[at]))
        out = mla_sparse_chunk_attention_tpu(
            q, kv_h, kv_f, sc_h, sc_f, thr, tie, t0, q_len, lim,
            latent=latent)                                        # [R,T,H,lat]
    else:
        scores, valid, onehot = _chunk_dense(sc_h, sc_f, t0, q_len, lim)
        with jax.named_scope("attn.index.select"):
            chosen = topk_mask(scores, valid, topk)               # [T, S+T]
            if probe is not None:
                _probe("chunk", *probe[:2], t0, q_len, probe[2], scores,
                       chosen)
        kv = jnp.concatenate(
            [kv_h, jnp.broadcast_to(kv_f[None], (kv_h.shape[0],)
                                    + kv_f.shape)], axis=1)
        bias = jnp.where(chosen[None] & onehot[:, :, None], 0.0,
                         DEFAULT_MASK_VALUE)                      # [R, T, S+T]
        out = mla_sparse_attention_reference(q[None], kv, bias, latent)
    # a token is kept by its own row alone: the others give it zeros
    return jnp.sum(out, axis=0)


def dsa_dense_attention(q, c, r_new, qi, *, positions, segment_ids,
                        index_heads: int, topk: int):
    """The same mathematics with no pool, in ``jax.numpy``: one flat axis of
    fresh tokens, token ``t`` sees the tokens of its segment at positions up
    to its own (the model's plain forward pass).  ``r_new`` is ``[k_pe |
    k_idx]``."""
    T, H, Dq = q.shape
    q_idx, w_idx = index_queries(qi, index_heads)
    Di = q_idx.shape[-1]
    r_new, i_new = r_new[..., :-Di], r_new[..., -Di:]
    W = c.shape[-1] + r_new.shape[-1]
    kv = _latent_rows(c, r_new, W, q.dtype)
    with jax.named_scope("attn.index.score"):
        scores = dsa_index_scores_reference(q_idx[None], w_idx[None],
                                            i_new[None])[0]
        valid = ((segment_ids[:, None] == segment_ids[None, :])
                 & (segment_ids[None, :] > 0)
                 & (positions[None, :] <= positions[:, None]))
    with jax.named_scope("attn.index.select"):
        chosen = topk_mask(scores, valid, topk)
    bias = jnp.where(chosen, 0.0, DEFAULT_MASK_VALUE)[None]
    return mla_sparse_attention_reference(q[None], kv[None], bias,
                                          c.shape[-1])[0]
