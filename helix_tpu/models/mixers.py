"""A kind of layer memory is ONE record here: the token mixers that keep a
fixed per-sequence state (``STATE_MIXERS``) and the attention kinds whose
memory is pages (``PAGE_KINDS``).

A layer whose memory is not pages (a gated short convolution's tail, power
retention's matrix, the gated delta rule's matrix and conv tail, a
sliding-window layer's ring of K/V, a Mamba-2 layer's state and conv tail)
keeps, for each sequence, a state of one
size whatever the sequence's length: a row of the STATE POOL
(``engine/kv_cache.py``).  ``STATE_MIXERS`` maps the kind's name (what
``ModelConfig.layer_types`` calls it) to a :class:`StateMixer`, which holds
everything the modules ABOVE ``models/`` ask of a kind:

- what it is called when an engine setting or a call is refused for it
  (``engine/engine.py::refuse_unsupported``, ``_refuse_call``);
- the arrays of one sequence's state in one layer, whether they take the page
  pool's dtype, whether a step hands back boundary states for the prefix
  cache, and the geometry its Pallas kernel takes;
- the look-back of one segment of a step (``rows_fn``: the function
  ``models/llama.py::_layer`` calls as ``state_fn``, built for the segment's
  rows) and of a forward pass with no engine (``oracle``);
- the host's account of a launch (``account``: increments of the engine's
  ``mixer_counts``), its levels from the host's mirrors (``gauges``), and the
  names under which ``/metrics``, ``helix.loop.launch`` and the flight record
  show them.

An ``attn`` layer's memory is PAGES, and what a page holds is a kind too:
K and V a kv head (``kv``), one latent row a token (``latent``), or that row
in one pool and the token's index key in a second under the same page ids
(``latent_indexed``).  ``PAGE_KINDS`` maps those names to a
:class:`PageKind` (``ModelConfig.page_kind`` resolves a model to its record,
never ``None``: a model with no ``attn`` layer is the K/V kind over zero
layers), which holds the same things under the same names (``refused_as``,
``refusals``, ``call_refusal``, ``check_geometry``, ``account``, ``series``,
``launch``, ``flight``) and, where a state kind says its arrays and its
look-back, the ARRAYS OF THE PAGE POOL (``pools``: what
``PagedKVCache.k_pages`` / ``v_pages`` hold for the kind, one page's shape,
whether an int8 pool keeps scales beside it), the fresh arrays a layer hands
on (``token_args``, ``token_arrays``), one segment's attention over the pool
(``attend``) and the chunk's query block (``query_block``).

A layer's COMPUTE (projections, gates, norms, rope) is ``models/llama.py``'s,
its operator ``ops/``'s.  A further kind is a record here, its compute
there, its ``ModelConfig`` keys and its tests (the fifth state kind,
``mamba2``, came so): nothing under ``engine/``, ``serving/`` or ``obs/``
spells a kind's name or reads the ``ModelConfig`` keys that tell one from
another.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

@dataclasses.dataclass(frozen=True)
class Series:
    """One series of ``/metrics``: ``value`` is the key of the engine's
    ``mixer_values()`` it shows, ``labels`` its own beside the model's."""
    name: str
    kind: str                    # "counter" | "gauge"
    value: str
    labels: tuple = ()           # ((label, value), ...)


@dataclasses.dataclass(frozen=True)
class StateMixer:
    # -- refused by name ---------------------------------------------------
    refused_as: str              # the property text of a refusal
    refusals: tuple              # ((setting, why), ...) by the keys of
                                 # ``engine/engine.py::_SETTINGS``; a setting
                                 # that is not here the kind is served with
    call_refusal: str            # why a call that moves pages is refused
    # -- its state -----------------------------------------------------------
    arrays: Callable             # cfg -> ((shape, dtype), ...) a layer and slot
    # -- its look-back -------------------------------------------------------
    rows_fn: Callable            # (rows, backend, *, cfg, decode, snap, packed,
                                 # window) -> one segment's ``state_fn``
    token_args: int              # leading arguments of ``state_fn`` that are
                                 # token arrays (``_segments_fn`` splits them)
    oracle: Callable             # (cfg, positions) -> the ``state_fn`` of a
                                 # forward pass with no engine
    # -- what shows it -------------------------------------------------------
    series: tuple                # of Series, in the order rendered
    launch: tuple                # ((attribute of helix.loop.launch, key), ...)
    flight: tuple = None         # ((field of the flight record, key), ...):
                                 # ``launch``'s unless given
    # -- what only some kinds have -------------------------------------------
    pool_dtype: bool = False     # the arrays take the PAGE POOL's dtype
    snapshots: bool = False      # a step hands back boundary states, which
                                 # the prefix cache files (``stateful``)
    check_geometry: Callable = None  # (cfg, tp, kv itemsize): the Pallas
                                 # kernel's; None: ``jax.numpy`` everywhere
    # the host's account of a launch, (cfg, cache_cfg, rows, pos, n_extra) ->
    # {count: increment}, every count at every launch; None: nothing counted
    account: Callable = None
    # (cfg, slots, steps) -> what the decode steps of one fused window hand
    # one another BESIDE the pool, empty (the last array of the state in the
    # carry, made by the program and dropped by it: a program starts from an
    # exact pool and leaves one); None: every step leaves the pool exact
    window: Callable = None
    gauges: Callable = None      # (cfg, pos) -> {level: value} from the live
                                 # positions; None: no level

    def __post_init__(self):
        if self.flight is None:
            object.__setattr__(self, "flight", self.launch)


@dataclasses.dataclass(frozen=True)
class Pool:
    """One array of the page pool, ``[layers with pages, pages, *page]``."""
    holds: str                   # "k" | "v" | "latent" | "index_keys"
    page: tuple                  # one page's shape in one layer
    scaled: bool = False         # it has a kv-head axis: an int8 pool keeps a
                                 # scale a token and head in a scale pool
                                 # beside it, and a mesh shards it by head;
                                 # without one it is bf16 or f32 on one device


@dataclasses.dataclass(frozen=True)
class PageKind:
    # -- refused by name (as a state kind's) ---------------------------------
    refused_as: str
    refusals: tuple
    call_refusal: str            # None: a call that moves pages is served
    # -- its pages -----------------------------------------------------------
    # (cfg, page size) -> the Pools, in the order ``PagedKVCache`` holds them:
    # the first is ``k_pages``, the second, where there is one, ``v_pages``
    pools: Callable
    geometry: Callable           # cfg -> ``(kv heads, head dim)`` as a snapshot
                                 # and the filestore's namespace state a pool
    token_args: int              # leading arguments of ``attn_fn`` that are
                                 # token arrays (``_segments_fn`` splits them)
    token_arrays: Callable       # cfg -> a token's shape in each of the two
                                 # fresh arrays a layer hands on to be cached
    # -- its attention over them ---------------------------------------------
    # (rows, tables, backend, *, cfg, bucket, has_hist, cold, mesh) -> one
    # segment's attention, called ``(q, k, v[, more], pool carry, layer)``;
    # None: the segment reads no page (``bucket`` tokens of prefill rows, none
    # with history: the packed kernel runs them)
    attend: Callable
    query_block: Callable        # (cfg, rung, rows) -> tokens in a query
                                 # block of the prefill segment's paged call
    check_geometry: Callable     # (cfg, tp, kv itemsize): the Pallas kernel's
    # -- what shows it (as a state kind's) -----------------------------------
    # the host's account of a launch, a state kind's arguments and the bucket
    # the rows lie in (``rung`` tokens, ``max_rows`` rows): the kernels size
    # their blocks from it
    account: Callable
    series: tuple
    launch: tuple
    flight: tuple = None
    gauges: Callable = None
    base: "PageKind" = None      # the kind whose pool this one's stand
                                 # beside: refused what that one is

    def __post_init__(self):
        if self.flight is None:
            object.__setattr__(self, "flight", self.launch)


# ---- the no-cache forms (a forward pass with no engine) -------------------


def short_conv(z, taps, prevs):
    """``y_t = sum_i taps[:, i] * z_{t-(K-1)+i}`` in f32: ``z [..., E]`` is
    the token's own gated input and ``prevs[d-1]`` the one ``d`` tokens
    back IN ITS SEQUENCE (zeros before the sequence's start)."""
    K = taps.shape[-1]
    w = taps.astype(jnp.float32)
    y = z.astype(jnp.float32) * w[:, K - 1]
    for d in range(1, K):
        y = y + prevs[d - 1].astype(jnp.float32) * w[:, K - 1 - d]
    return y


def whole_sequence_conv_fn(z, taps, layer_cache):
    """The conv look-back of a forward pass with no cache: every row of
    ``z [B, S, E]`` is one sequence from its start, so tap ``d`` is the
    row shifted by ``d`` with zeros before it."""
    S = z.shape[1]
    prevs = [jnp.pad(z, ((0, 0), (d, 0), (0, 0)))[:, :S]
             for d in range(1, taps.shape[-1])]
    return short_conv(z, taps, prevs), None


def whole_sequence_retention_fn(q, k, v, log_g, layer_cache):
    """The retention of a forward pass with no cache: every row of the
    batch is one sequence from its start, so the definition's quadratic
    form runs as it is."""
    from helix_tpu.ops.retention import retention_quadratic

    return retention_quadratic(q, k, v, log_g), None


def deltanet_heads(y, cfg):
    """The convolution's output ``y [..., channels]`` (float32) through its
    SiLU, as the rule's ``q, k, v`` (``ops.deltanet.split_heads``)."""
    from helix_tpu.ops.deltanet import split_heads

    return split_heads(
        jax.nn.silu(y), cfg.linear_key_heads, cfg.linear_value_heads,
        cfg.linear_key_dim, cfg.linear_value_dim)


def whole_sequence_deltanet_fn(x, g, beta, taps, layer_cache, cfg):
    """The delta-rule layer of a forward pass with no cache: every row of
    the batch is one sequence from its start, so the convolution looks back
    into zeros and the rule runs from a zero state."""
    from helix_tpu.ops.deltanet import delta_sequence

    with jax.named_scope("deltanet.conv"):
        q, k, v = deltanet_heads(
            whole_sequence_conv_fn(x, taps, None)[0], cfg)
    with jax.named_scope("deltanet.mix"):
        S0 = jnp.zeros(v.shape[2:3] + (q.shape[-1], v.shape[-1]), jnp.float32)
        return jax.vmap(
            lambda *a: delta_sequence(*a, S0)[0])(q, k, v, g, beta), None


def mamba2_heads(y, bias, cfg):
    """The convolution's output ``y [..., channels]`` (float32) plus its bias
    through SiLU, as the state space's ``x [..., heads, head dim]`` and ``B, C
    [..., groups, state]``."""
    y = jax.nn.silu(y + bias.astype(jnp.float32))
    lead, G, N = y.shape[:-1], cfg.mamba_groups, cfg.mamba_state_size
    x, Bm, Cm = jnp.split(
        y, [cfg.mamba_inner, cfg.mamba_inner + G * N], axis=-1)
    return (x.reshape(lead + (cfg.mamba_heads, cfg.mamba_head_dim)),
            Bm.reshape(lead + (G, N)), Cm.reshape(lead + (G, N)))


def whole_sequence_mamba2_fn(xbc, dt, la, taps, bias, D, layer_cache, cfg):
    """The Mamba-2 layer of a forward pass with no cache: every row of the
    batch is one sequence from its start, so the convolution looks back into
    zeros and the state space runs from a zero state."""
    from helix_tpu.ops.ssd import ssd_sequence

    with jax.named_scope("ssd.conv"):
        x, Bm, Cm = mamba2_heads(
            whole_sequence_conv_fn(xbc, taps, None)[0], bias, cfg)
    with jax.named_scope("ssd.kernel"):
        h0 = jnp.zeros(x.shape[2:] + (Bm.shape[-1],), jnp.float32)
        y = jax.vmap(lambda *a: ssd_sequence(
            *a, h0, chunk=cfg.mamba_chunk)[0])(x, dt, la, Bm, Cm)
        return y + D.astype(jnp.float32)[:, None] * x, None


def whole_sequence_window_fn(q, k, v, layer_cache, *, positions, window):
    """The window layer of a forward pass with no cache: every row of the
    batch is one sequence from its start, so the definition's masks run as
    they are (causal, and key ``j`` hidden from query ``i`` where ``i - j >=
    window``)."""
    from helix_tpu.ops.attention import attention

    return attention(
        q, k, v, causal=True, q_positions=positions, kv_positions=positions,
        window=window), None


# ---- one segment of a step -------------------------------------------------
#
# ``rows = (t0, qlen, hist, slots)``: row ``r`` of the segment is the
# ``qlen[r]`` tokens from ``t0[r]`` of the sequence in slot ``slots[r]``,
# which has ``hist[r]`` tokens behind it.  A row that starts its sequence
# starts from zeros; a row with no fresh token (an idle slot, padding) and a
# row without a slot write nothing.  The function a ``rows_fn`` returns is
# called with the mixer's arrays and ``((page carry, kacc, vacc, state
# pool[, snaps]), the layer's index among its kind's)``: the pools ride the
# carry and are updated IN PLACE.  ``decode``: the rows are one token each
# and row ``b`` is slot ``b``; ``window = (step, n_extra)`` then says which
# step of a fused window of ``1 + n_extra`` decode steps this is (both may be
# traced: the stage is step 0, the tail's step ``t`` is ``t + 1``).


def _state_after(zf, S, t0, n):
    """The conv state of each row after ``n [R]`` of its fresh tokens: the
    last ``K - 1`` of (the state it came with, its first ``n`` tokens),
    oldest first.  ``zf [T, E]`` flat, ``S [R, K - 1, E]``, ``t0 [R]``."""
    K1 = S.shape[1]
    T = zf.shape[0]
    out = []
    for i in range(K1):
        at = n + i - K1                      # offset in the row, < 0: in S
        fresh = zf[jnp.clip(t0 + at, 0, T - 1)]
        old = S[:, 0]
        for m in range(1, K1):
            old = jnp.where((n + i == m)[:, None], S[:, m], old)
        out.append(jnp.where((at >= 0)[:, None], fresh, old))
    return jnp.stack(out, axis=1)


def _conv_rows(z, taps, pool, lc, t0, qlen, hist, slots, snap=None):
    """A causal depthwise convolution over one segment of the step, its
    tokens row after row on one flat axis.  ``z [B, S, E]``, ``taps [E,
    K]``, ``pool [layers, slots, K - 1, E]`` read and written at layer
    ``lc``.

    A token's tap ``d`` back is its flat neighbour if that is in its own
    row, else its row's state (zeros for a row that starts its sequence):
    never the neighbour row's token.  The row's new state, the last ``K -
    1`` of (state, the row's inputs), is written to its slot; a slot index
    past the pool writes nothing.  Returns ``(y float32, pool, each row's
    state after snap[r] of its tokens or None)``."""
    Bz, Sz, E = z.shape
    T, K1 = Bz * Sz, taps.shape[-1] - 1
    zf = z.reshape(T, E)
    nslots = pool.shape[1]
    S = pool[lc][jnp.clip(slots, 0, nslots - 1)]            # [R, K-1, E]
    S = jnp.where((hist > 0)[:, None, None], S, 0).astype(z.dtype)
    prevs = []
    for d in range(1, K1 + 1):
        if Sz == 1:
            # one-token rows (a decode step): every tap is the state
            prev = S[:, K1 - d]
        else:
            prev = jnp.pad(zf, ((d, 0), (0, 0)))[:T]
            for j in range(d):
                # the row's token j reaches d back past its start
                at = jnp.where(qlen > j, t0 + j, T)
                prev = prev.at[at].set(S[:, K1 + j - d], mode="drop")
        prevs.append(prev.reshape(z.shape))
    y = short_conv(z, taps, prevs)
    new = _state_after(zf, S, t0, qlen).astype(pool.dtype)
    dest = jnp.where(qlen > 0, slots, nslots)
    pool = pool.at[lc, dest].set(new, mode="drop")
    return y, pool, None if snap is None else _state_after(
        zf, S, t0, snap).astype(pool.dtype)


def _conv_rows_fn(rows, backend, *, cfg, decode, snap=None, packed=None,
                  window=None):
    """A conv layer's look-back (``models/llama.py::_conv_mixer``):
    ``_conv_rows`` on the state pool in the carry.  ``snap [R]``: also hand
    back each row's state after that many of its tokens (what a prefix hit
    resumes from), stacked over the conv layers in the carry's last element
    (a segment without ``snap`` passes that element on).  Called ``(z, taps,
    carry)``."""

    def conv_fn(z, taps, carry_cache):
        (caches, kacc, vacc, pool, *snaps), lc = carry_cache
        y, pool, after = _conv_rows(z, taps, pool, lc, *rows, snap)
        if after is not None:
            snaps = [snaps[0].at[lc].set(after)]
        return y, (caches, kacc, vacc, pool, *snaps)

    return conv_fn


def _retention_rows_fn(rows, backend, *, cfg, decode, snap=None, packed=None,
                       window=None):
    """A retention layer's sum (``models/llama.py::_retention_mixer``) over
    a state thousands of times a conv state's size, the pair ``(S pool, Z
    pool)``.  ``decode``: the recurrence applied once; on a TPU one pass of
    the decode kernel over the live slots, which READS ``S`` at every step
    of a fused window and writes it at the window's last
    (``ops/retention.py::retention_window_step``: the window's tokens ride
    the carry behind the pools, ``_retention_window``).  Else the rows are
    runs of fresh tokens on one flat axis (the chunked form: on a TPU what
    reads no state once for the axis, then the chunk kernel a row, which
    reads a row's state once, or not at all where the row starts its
    sequence, and writes it once).  Called ``(q, k, v, log_g, carry)``."""
    from helix_tpu.ops.retention import retention_rows, retention_window_step

    t0, qlen, hist, slots = rows

    def retention_fn(q, k, v, log_g, carry_cache):
        (caches, kacc, vacc, (s_pool, z_pool, *pending)), lc = carry_cache
        Bq, Sq, H, D = q.shape
        if decode:
            # a window's tokens have a layer axis like the pools'; without
            # them every step is a window of one
            step, n_extra = window if pending else (0, 0)
            y, s_pool, z_pool, mine = retention_window_step(
                q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], s_pool, z_pool,
                jax.tree.map(lambda a: a[lc], pending[0]) if pending
                else None, lc, qlen > 0, step, step == n_extra,
                backend=backend)
            if pending:
                pending = [jax.tree.map(
                    lambda a, m: a.at[lc].set(m), pending[0], mine)]
        else:
            y, s_pool, z_pool = retention_rows(
                q.reshape(Bq * Sq, H, D), k.reshape(Bq * Sq, -1, D),
                v.reshape(Bq * Sq, -1, D), log_g.reshape(Bq * Sq, -1),
                t0, qlen, hist, slots, s_pool, z_pool, lc, backend=backend)
        return y.reshape(Bq, Sq, H, D), (caches, kacc, vacc,
                                         (s_pool, z_pool, *pending))

    return retention_fn


def _deltanet_rows_fn(rows, backend, *, cfg, decode, snap=None, packed=None,
                      window=None):
    """A delta-rule layer's look-back (``models/llama.py::_deltanet_mixer``)
    over TWO states a slot: the convolution's tail (``_conv_rows``, the same
    look-back as a gated short convolution's at 4 taps over the q | k | v
    channels) and the float32 matrix a value head, the pair ``(conv pool, S
    pool)``.  ``decode``: the rule applied once (on a TPU one pass of the
    decode kernel over the live slots).  Else the chunked form, 64 tokens at
    a time: what does not read the state for all the rows' chunks at once,
    then the chunks in order, on a TPU in the chunk kernel.  Called ``(x
    W_qkv, g, beta, taps, carry)``."""
    from helix_tpu.ops.deltanet import delta_decode, delta_rows

    t0, qlen, hist, slots = rows

    def deltanet_fn(x, g, beta, taps, carry_cache):
        (caches, kacc, vacc, (c_pool, s_pool)), lc = carry_cache
        Bx, Sx, _ = x.shape
        with jax.named_scope("deltanet.conv"):
            y, c_pool, _ = _conv_rows(
                x, taps, c_pool, lc, t0, qlen, hist, slots)
            q, k, v = deltanet_heads(y, cfg)
        with jax.named_scope("deltanet.mix"):
            if decode:
                o, s_pool = delta_decode(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s_pool,
                    lc, qlen > 0, backend=backend)
            else:
                flat = lambda a: a.reshape((Bx * Sx,) + a.shape[2:])
                o, s_pool = delta_rows(
                    flat(q), flat(k), flat(v), flat(g), flat(beta), t0,
                    qlen, hist, slots, s_pool, lc, backend=backend)
        return o.reshape((Bx, Sx) + o.shape[1:]), (
            caches, kacc, vacc, (c_pool, s_pool))

    return deltanet_fn


def _mamba2_rows_fn(rows, backend, *, cfg, decode, snap=None, packed=None,
                    window=None):
    """A Mamba-2 layer's look-back (``models/llama.py::_mamba2_mixer``) over
    TWO states a slot: the convolution's tail (``_conv_rows`` at 4 taps over
    the x | B | C channels) and the float32 array ``h`` a head, the pair
    ``(conv pool, h pool)``.  ``decode``: the recurrence applied once; on a
    TPU one pass of the decode kernel over the live slots, which READS ``h``
    at every step of a fused window and writes it at the window's last
    (``ops/ssd.py::ssd_window_step``: the window's tokens ride the carry
    behind the pools, ``_mamba2_window``; the conv tail steps every time).
    Else the chunked form at the published block, a row's state read at its
    first block and written at its last: on a TPU what does not read the
    state for the rows' blocks at once, then the blocks in order in the
    chunk kernel (``ops/ssd.py::ssd_rows``); on a CPU a loop over the blocks.
    Called ``(x W_xBC, dt, dt * A, taps, bias, D, carry)``."""
    from helix_tpu.ops.ssd import ssd_rows, ssd_window_step

    t0, qlen, hist, slots = rows

    def mamba2_fn(xbc, dt, la, taps, bias, D, carry_cache):
        (caches, kacc, vacc, (c_pool, h_pool, *pending)), lc = carry_cache
        Bx, Sx, _ = xbc.shape
        with jax.named_scope("ssd.conv"):
            y, c_pool, _ = _conv_rows(
                xbc, taps, c_pool, lc, t0, qlen, hist, slots)
            x, Bm, Cm = mamba2_heads(y, bias, cfg)
        with jax.named_scope("ssd.kernel"):
            if decode:
                # without a window's tokens every step is a window of one
                step, n_extra = window if pending else (0, 0)
                o, h_pool, mine = ssd_window_step(
                    x[:, 0], dt[:, 0], la[:, 0], Bm[:, 0], Cm[:, 0], h_pool,
                    pending[0] if pending else None, lc, qlen > 0, step,
                    step == n_extra, backend=backend)
                pending = [mine] if pending else []
            else:
                flat = lambda a: a.reshape((Bx * Sx,) + a.shape[2:])
                o, h_pool = ssd_rows(
                    flat(x), flat(dt), flat(la), flat(Bm), flat(Cm), t0,
                    qlen, hist, slots, h_pool, lc, chunk=cfg.mamba_chunk,
                    backend=backend)
            o = o.reshape(x.shape) + D.astype(jnp.float32)[:, None] * x
        return o, (caches, kacc, vacc, (c_pool, h_pool, *pending))

    return mamba2_fn


def _window_rows_fn(rows, backend, *, cfg, decode, snap=None, packed=None,
                    window=None):
    """A window layer's attention (``models/llama.py::_layer``): of a row's
    ``hist[r]`` tokens its slot's rings ``(K rings, V rings)`` hold the last
    ``W``.  The row's queries read the rings AS THEY STAND
    (``ops.window.window_attention``: a decode step's one-token rows, a
    chunk that continues a prompt), its fresh K/V beside them; then its
    fresh K/V land in the rings (``write_ring``: a chunk of ``W`` replaces
    the ring, a shorter one rotates into it).  A ring is not cleared for a
    new sequence: a row with no history reads none of it and the mask by
    position hides what it has not written.

    ``packed = (positions, segment ids, mesh)``: no row of the segment has
    history (a cold packed wave, a first chunk), so its attention is the
    packed self-attention under the window beside the segment mask, with no
    pool read.  Called ``(q, k, v, carry)``."""
    from helix_tpu.ops.attention import attention as full_attention
    from helix_tpu.ops.window import window_attention, write_ring

    t0, qlen, hist, slots = rows

    def window_fn(q, k, v, carry_cache):
        (caches, kacc, vacc, (k_ring, v_ring)), lc = carry_cache
        Bq, Sq, H, D = q.shape
        flat = lambda a: a.reshape((Bq * Sq,) + a.shape[2:])
        if packed is not None:
            pos, seg, mesh = packed
            out = full_attention(
                q, k, v, causal=True, q_positions=pos, kv_positions=pos,
                q_segment_ids=seg, kv_segment_ids=seg, backend=backend,
                mesh=mesh, window=k_ring.shape[2])
        else:
            out = window_attention(
                flat(q), flat(k), flat(v), k_ring, v_ring, lc, t0, qlen,
                hist, slots, backend=backend, max_q_len=Sq,
            ).reshape(q.shape)
        k_ring, v_ring = write_ring(
            k_ring, v_ring, lc, flat(k), flat(v), t0, qlen, hist, slots)
        return out, (caches, kacc, vacc, (k_ring, v_ring))

    return window_fn


# ---- the arrays of one sequence's state in one layer ----------------------


def _conv_arrays(cfg) -> tuple:
    """Its last ``conv_kernel - 1`` gated inputs, oldest first, in the
    model's dtype."""
    return (((cfg.conv_kernel - 1, cfg.hidden_size), cfg.dtype),)


def _retention_arrays(cfg) -> tuple:
    """The matrix ``S [kv heads, D_held, head_dim]`` and the normaliser ``Z
    [kv heads, head_dim, head_dim]``, float32 (running sums over the whole
    context)."""
    from helix_tpu.ops.retention import held_rows

    if cfg.retention_degree != 2:
        raise ValueError(
            f"{cfg.name}: power retention of degree "
            f"{cfg.retention_degree} is not supported: only 2")
    d, kvh = cfg.head_dim, cfg.num_kv_heads
    return (((kvh, held_rows(d), d), "float32"),
            ((kvh, d, d), "float32"))


def _retention_window(cfg, slots: int, steps: int) -> tuple:
    """A fused window's tokens (``ops/retention.py::window_zeros``) a
    retention layer: ~2 MB a layer at 24 slots and 8 steps, float32."""
    from helix_tpu.ops.retention import window_zeros

    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.num_state_layers,) + a.shape),
        window_zeros(slots, cfg.num_kv_heads, cfg.head_dim, steps))


def _deltanet_arrays(cfg) -> tuple:
    """The conv's tail, the last ``conv_kernel - 1`` rows of its q|k|v
    channels in the model's dtype, and the matrix ``S [value heads, key dim,
    value dim]`` float32."""
    return (((cfg.conv_kernel - 1, cfg.deltanet_channels), cfg.dtype),
            ((cfg.linear_value_heads, cfg.linear_key_dim,
              cfg.linear_value_dim), "float32"))


def _mamba2_arrays(cfg) -> tuple:
    """The conv's tail, the last ``conv_kernel - 1`` rows of its x|B|C
    channels in the model's dtype, and the state ``h`` float32 as the pool
    holds it (``ops/ssd.py``: ``pack`` heads to a lane tile, ``[heads /
    pack, state, pack * head dim]``: the bytes of ``[heads, head dim,
    state]``)."""
    from helix_tpu.ops.ssd import head_pack

    k = head_pack(cfg.mamba_head_dim)
    if cfg.mamba_heads % (k * cfg.mamba_groups):
        raise ValueError(
            f"{cfg.name}: {cfg.mamba_heads} Mamba-2 heads of "
            f"{cfg.mamba_head_dim} in {cfg.mamba_groups} groups: a group's "
            f"heads must fill whole rows of {k} heads in the state pool")
    return (((cfg.conv_kernel - 1, cfg.mamba_channels), cfg.dtype),
            ((cfg.mamba_heads // k, cfg.mamba_state_size,
              k * cfg.mamba_head_dim), "float32"))


def _mamba2_window(cfg, slots: int, steps: int) -> tuple:
    """A fused window's tokens (``ops/ssd.py::window_zeros``) of the Mamba-2
    layers: 37 KB a layer, slot and step, float32."""
    from helix_tpu.ops.ssd import window_zeros

    return window_zeros(
        cfg.num_state_layers, slots, cfg.mamba_heads, cfg.mamba_head_dim,
        cfg.mamba_groups, cfg.mamba_state_size, steps)


def _window_arrays(cfg) -> tuple:
    """The K ring and the V ring ``[sliding_window, kv heads, head_dim]``
    (in the pool's dtype: ``pool_dtype``)."""
    if cfg.sliding_window <= 0:
        raise ValueError(
            f"{cfg.name}: window layers need sliding_window > 0")
    ring = (cfg.sliding_window, cfg.num_kv_heads, cfg.head_dim)
    return ((ring, cfg.dtype), (ring, cfg.dtype))


# ---- the Pallas kernels' geometry ------------------------------------------


def _check_retention(cfg, tp, itemsize) -> None:
    from helix_tpu.ops.retention_kernel import check_retention_geometry

    check_retention_geometry(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)


def _check_deltanet(cfg, tp, itemsize) -> None:
    from helix_tpu.ops.deltanet_kernel import check_deltanet_geometry

    check_deltanet_geometry(
        cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_dim,
        cfg.linear_value_dim)


def _check_mamba2(cfg, tp, itemsize) -> None:
    from helix_tpu.ops.ssd_kernel import check_ssd_geometry

    check_ssd_geometry(cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups,
                       cfg.mamba_state_size, cfg.mamba_chunk)


def _check_window(cfg, tp, itemsize) -> None:
    """The window kernel takes what the ragged kernel takes, at the window
    layers' own count of query heads."""
    from helix_tpu.ops.paged_kernel import check_geometry

    check_geometry(
        cfg.heads_of("window") // tp, max(cfg.num_kv_heads // tp, 1),
        cfg.head_dim, itemsize)


# ---- the host's account of a launch ----------------------------------------
#
# ``rows``: the plan's prefill rows (``engine/ragged.py``: ``start`` tokens
# behind a row, ``rem`` fresh, ``slot`` or -1), ``pos`` the positions of the
# live decode rows, each of which runs once a fused step (``1 + n_extra``
# steps a launch), a token further on each.


def _matrix_rows(cfg, cache_cfg, rows, pos, n_extra) -> dict:
    """The rows of a matrix state's pool a launch reads and writes: a live
    decode row once a fused step, a prefill row with a slot once; each row
    is every layer's state of one slot, read once and written once."""
    dec = len(pos) * (1 + int(n_extra))
    held = [r for r in rows if r.slot >= 0]
    return {
        "decode_rows": dec, "chunk_rows": len(held),
        "state_bytes_touched": 2 * (dec + len(held)) * (
            cache_cfg.state_bytes(cfg) // cache_cfg.state_slots),
    }


def _rows_from_zeros(cfg, cache_cfg, rows, pos, n_extra) -> dict:
    """... and of the chunk rows, those that start their sequence: the chunk
    kernel skips the state's read and its query for them."""
    return {
        **_matrix_rows(cfg, cache_cfg, rows, pos, n_extra),
        "chunk_rows_from_zeros": sum(
            1 for r in rows if r.slot >= 0 and r.start == 0),
    }


def _written_once_a_window(which: int, rows_of) -> Callable:
    """... (``rows_of``) and of the decode row-steps, those that WROTE the
    state: a fused window of ``1 + n_extra`` steps reads a live row's matrix
    (array ``which`` of the kind's) at every step and writes it at its last
    (what the kind keeps beside it, a few percent of the bytes, at every
    step), so writes over ``decode_rows`` is the share of steps that paid
    for a write and the bytes touched follow what moved.  As the kernel's
    path runs it: the CPU's recurrence, its oracle, writes at every step."""

    def account(cfg, cache_cfg, rows, pos, n_extra) -> dict:
        out = rows_of(cfg, cache_cfg, rows, pos, n_extra)
        s, sdt = cfg.state_arrays()[which]
        unwritten = out["decode_rows"] - len(pos)
        out["state_writes"] = len(pos)
        out["state_bytes_touched"] -= unwritten * cfg.num_state_layers * int(
            np.prod(s)) * jnp.dtype(sdt).itemsize
        return out

    return account


_retention_account = _written_once_a_window(0, _rows_from_zeros)


def _chunked_rows(chunk_of, rows_of=_matrix_rows) -> Callable:
    """... (``rows_of``) and the chunks the chunked form runs: a prefill row's
    ``ceil(rem / chunk_of(cfg))`` in every layer of the kind (for the delta
    rule ``ops/deltanet.py::chunk_table``'s live entries).  Device time under
    the kind's kernel scope in the programs that carry a chunk, over this
    count, is the cost of a chunk (PERF.md section 5)."""

    def account(cfg, cache_cfg, rows, pos, n_extra) -> dict:
        return {
            **rows_of(cfg, cache_cfg, rows, pos, n_extra),
            "chunks": sum(-(-r.rem // chunk_of(cfg)) for r in rows) * (
                cfg.num_state_layers),
        }

    return account


def _delta_chunk(cfg) -> int:
    from helix_tpu.ops.deltanet import CHUNK

    return CHUNK


_deltanet_account = _chunked_rows(_delta_chunk)
# the published block of the state space's chunked form
# ... with the rows that start their sequence: the chunk kernel skips the
# state's read and its product for their first block
_mamba2_account = _written_once_a_window(1, _chunked_rows(
    lambda cfg: cfg.mamba_chunk, _rows_from_zeros))


def _window_account(cfg, cache_cfg, rows, pos, n_extra) -> dict:
    """The rows of a launch that read and write their slot's rings.  A row
    reads ``min(tokens behind it, W)`` ring rows of K and of V in every
    window layer and writes its fresh tokens' (at most ``W``); the bytes of
    live ring rows read are what a roofline reckoned from a trace divides
    by, ``state_bytes_touched`` counts the ring rows written.
    ``query_blocks``: the programs the window kernel runs for the launch's
    chunk rows, a row's ``ceil(tokens / block)`` in every window layer (the
    block is the kernel's own, ``chunk_query_block``: 128 tokens at a query
    group of 8, the same count under any bucket that holds the row); none
    where no row has history: the packed flash kernel runs those."""
    from helix_tpu.ops.paged_kernel import chunk_query_block

    W = cfg.sliding_window
    block = chunk_query_block(
        -(-max((r.rem for r in rows), default=8) // 8) * 8,
        cfg.heads_of("window") // cfg.num_kv_heads)
    per_tok = (2 * cfg.num_kv_heads * cfg.head_dim * jnp.dtype(
        cache_cfg.dtype).itemsize * cfg.num_state_layers)
    steps = 1 + int(n_extra)
    read = sum(int(np.minimum(pos + k, W).sum()) for k in range(steps))
    wrote = len(pos) * steps
    read += sum(min(r.start, W) for r in rows)
    wrote += sum(min(r.rem, W) for r in rows if r.slot >= 0)
    return {
        "decode_rows": len(pos) * steps, "chunk_rows": len(rows),
        "query_blocks": cfg.num_state_layers * any(
            r.start > 0 for r in rows) * sum(-(-r.rem // block) for r in rows),
        "ring_bytes_read": read * per_tok,
        "state_bytes_touched": wrote * per_tok,
    }


def _window_gauges(cfg, pos) -> dict:
    """Running rows whose sequence has passed the window: their ring has
    wrapped."""
    return {"rows_wrapped": int(np.count_nonzero(pos >= cfg.sliding_window))}


def _rows_series(name: str) -> tuple:
    """The rows of the pool the steps advanced (one token at a time, or a
    chunk of a prompt), by the form that ran them."""
    return (Series(name, "counter", "chunk_rows", (("kind", "chunk"),)),
            Series(name, "counter", "decode_rows", (("kind", "decode"),)))


# the pool's bytes, and the bytes its rows moved
_POOL_BYTES = Series("helix_recurrent_state_bytes", "gauge", "pool_bytes")
_BYTES_TOUCHED = Series(
    "helix_state_bytes_touched_total", "counter", "state_bytes_touched")


STATE_MIXERS = {
    "conv": StateMixer(
        refused_as="recurrent state (gated short convolutions)",
        refusals=(
            ("multi_device",
             "the state pool and the conv operator are single-device"),
            ("int8_kv", "the page pool beside a state pool is bf16 or "
             "f32"),
            ("adapters", "no LoRA targets on the conv projections"),
            ("spec_decode",
             "a rejected draft would have to roll the conv state back"),
            ("tiered",
             "a demoted cold middle is resumed without the state at its end"),
            ("host_tier",
             "a spilled prefix or a preempted sequence's pages come back "
             "without the state"),
        ),
        # served with the prefix cache: a prefix is pages AND the conv state
        # at its end, filed by the steps that pass a page boundary
        call_refusal="the sequence's conv state has no place in what it "
                     "moves",
        arrays=_conv_arrays,
        snapshots=True,
        rows_fn=_conv_rows_fn,
        token_args=1,
        oracle=lambda cfg, positions: whole_sequence_conv_fn,
        series=(_POOL_BYTES,),
        launch=(("conv_layers", "layers"),),
    ),
    "retention": StateMixer(
        refused_as="a matrix state (power retention)",
        refusals=(
            ("multi_device",
             "the state pool and the retention kernel are single-device"),
            ("int8_kv",
             "no page holds bytes, and the state is a float32 running sum"),
            ("adapters", "no LoRA targets on the retention "
             "projections"),
            ("spec_decode",
             "a rejected draft would have to roll the matrix state back"),
            ("tiered", "there is no page of KV to demote"),
            ("host_tier",
             "a preempted sequence's state (tens of MB a layer) has no host "
             "tier"),
            ("prefix_cache",
             "a filed state is tens of MB a layer: a snapshot budget and an "
             "eviction of its own; set enable_prefix_cache: false"),
        ),
        call_refusal="the sequence's state has no place in what it moves, "
                     "and it has no page of KV",
        arrays=_retention_arrays,
        check_geometry=_check_retention,
        rows_fn=_retention_rows_fn,
        token_args=4,
        oracle=lambda cfg, positions: whole_sequence_retention_fn,
        account=_retention_account,
        window=_retention_window,
        series=(
            _POOL_BYTES,
            *_rows_series("helix_retention_rows_total"),
            # over kind="decode" above, the share of decode steps that wrote
            # the state (1 where no window is fused)
            Series("helix_retention_state_writes_total", "counter",
                   "state_writes"),
            # over kind="chunk" above, the share of rows the state's query
            # was skipped for
            Series("helix_retention_chunk_rows_from_zeros_total", "counter",
                   "chunk_rows_from_zeros"),
            _BYTES_TOUCHED,
        ),
        launch=(("retention_layers", "layers"),
                ("retention_chunk_rows", "chunk_rows"),
                ("retention_chunk_rows_from_zeros", "chunk_rows_from_zeros"),
                ("retention_state_writes", "state_writes")),
        flight=(("retention_chunk_rows", "chunk_rows"),
                ("retention_chunk_rows_from_zeros", "chunk_rows_from_zeros")),
    ),
    "deltanet": StateMixer(
        refused_as="a matrix state and a conv tail (gated delta rule)",
        refusals=(
            ("multi_device",
             "the state pool and the delta-rule kernel are single-device"),
            ("int8_kv",
             "the pool beside a state pool is bf16 or f32, and the state is "
             "a float32 matrix"),
            ("adapters", "no LoRA targets on the delta-rule "
             "projections"),
            ("spec_decode",
             "a rejected draft would have to roll the matrix state back"),
            ("tiered",
             "a demoted cold middle is resumed without the state at its end"),
            ("host_tier",
             "a preempted sequence's state (megabytes a layer) has no host "
             "tier"),
            ("prefix_cache",
             "a filed state is megabytes a layer: a snapshot budget and an "
             "eviction of its own; set enable_prefix_cache: false"),
        ),
        call_refusal="the sequence's state has no place in what it moves",
        arrays=_deltanet_arrays,
        check_geometry=_check_deltanet,
        rows_fn=_deltanet_rows_fn,
        token_args=3,
        oracle=lambda cfg, positions: functools.partial(
            whole_sequence_deltanet_fn, cfg=cfg),
        account=_deltanet_account,
        series=(
            # device time under deltanet.mix in the programs that carry a
            # chunk, over this, is a chunk's cost
            Series("helix_deltanet_chunks_total", "counter", "chunks"),
            _POOL_BYTES,
            *_rows_series("helix_deltanet_rows_total"),
            _BYTES_TOUCHED,
        ),
        launch=(("deltanet_layers", "layers"), ("deltanet_chunks", "chunks")),
    ),
    "window": StateMixer(
        refused_as="a ring of K/V a slot (sliding-window attention)",
        refusals=(
            ("multi_device",
             "the rings and the window kernel are single-device"),
            ("int8_kv",
             "the rings and the pages beside them are bf16 or f32: a ring "
             "row has no scale"),
            ("adapters", "no LoRA targets on the window layers' "
             "projections"),
            ("spec_decode",
             "a rejected draft's K/V would have overwritten ring rows the "
             "window still needs"),
            ("tiered",
             "a demoted cold middle is resumed without the ring at its end"),
            ("host_tier",
             "a spilled prefix or a preempted sequence's pages come back "
             "without the ring"),
            ("prefix_cache",
             "a hit would need the ring as it stood at the prefix's "
             "boundary: no step files it; set enable_prefix_cache: false"),
        ),
        call_refusal="the sequence's rings have no place in what it moves",
        arrays=_window_arrays,
        pool_dtype=True,
        check_geometry=_check_window,
        rows_fn=_window_rows_fn,
        token_args=3,
        oracle=lambda cfg, positions: functools.partial(
            whole_sequence_window_fn, positions=positions,
            window=cfg.sliding_window),
        account=_window_account,
        gauges=_window_gauges,
        series=(
            # rows x min(length, W) x a token's K and V x window layers: what
            # a roofline by hand divides by
            Series("helix_window_ring_bytes_read_total", "counter",
                   "ring_bytes_read"),
            _POOL_BYTES,
            *_rows_series("helix_window_rows_total"),
            # over kind="chunk" above x window layers: the window kernel's
            # programs a chunk row (4 a 512-token row at a block of 128)
            Series("helix_window_query_blocks_total", "counter",
                   "query_blocks"),
            _BYTES_TOUCHED,
        ),
        launch=(("window_layers", "layers"),
                ("window_rows_wrapped", "rows_wrapped")),
    ),
    "mamba2": StateMixer(
        refused_as="a state-space state and a conv tail (Mamba-2)",
        refusals=(
            ("multi_device",
             "the state pool and the state-space kernel are single-device"),
            ("int8_kv",
             "the pool beside a state pool is bf16 or f32, and the state is "
             "a float32 array"),
            ("adapters", "no LoRA targets on the Mamba-2 projections"),
            ("spec_decode",
             "a rejected draft would have to roll the state back"),
            ("tiered",
             "a demoted cold middle is resumed without the state at its end"),
            ("host_tier",
             "a preempted sequence's state (megabytes a layer) has no host "
             "tier"),
            ("prefix_cache",
             "a filed state is megabytes a layer: a snapshot budget and an "
             "eviction of its own; set enable_prefix_cache: false"),
        ),
        call_refusal="the sequence's state has no place in what it moves",
        arrays=_mamba2_arrays,
        check_geometry=_check_mamba2,
        rows_fn=_mamba2_rows_fn,
        token_args=3,
        oracle=lambda cfg, positions: functools.partial(
            whole_sequence_mamba2_fn, cfg=cfg),
        account=_mamba2_account,
        window=_mamba2_window,
        series=(
            # device time under ssd.kernel in the programs that carry a
            # chunk, over this, is a block's cost
            Series("helix_ssd_chunks_total", "counter", "chunks"),
            _POOL_BYTES,
            *_rows_series("helix_ssd_rows_total"),
            # over kind="decode" above, the share of decode steps that wrote
            # the state (1 where no window is fused)
            Series("helix_ssd_state_writes_total", "counter", "state_writes"),
            # over kind="chunk" above, the share of rows whose first block
            # skipped the state's read and its product
            Series("helix_ssd_chunk_rows_from_zeros_total", "counter",
                   "chunk_rows_from_zeros"),
            _BYTES_TOUCHED,
        ),
        launch=(("ssd_layers", "layers"), ("ssd_chunks", "chunks"),
                ("ssd_chunk_rows", "chunk_rows"),
                ("ssd_chunk_rows_from_zeros", "chunk_rows_from_zeros"),
                ("ssd_state_writes", "state_writes")),
    ),
}


# ---- the kinds of page -------------------------------------------------------
#
# ``rows = (t0, q_len, hist)`` of a segment as a state kind's, ``tables`` its
# rows' page tables.  The function an ``attend`` returns flattens the token
# grid onto the op's flat row axis; ``caches`` is the pool carry
# (``PagedKVCache.carry``) and ``lyr`` the layer's index among those with
# pages.


def _kv_pools(cfg, page_size: int) -> tuple:
    """K and V ``[P, KVH, D]``; a head width that divides the 128 lanes is
    stored ``[KVH / pack, pack * D]`` (``ModelConfig.kv_head_pack``)."""
    pack = cfg.kv_head_pack
    page = (page_size, cfg.num_kv_heads // pack, cfg.head_dim * pack)
    return Pool("k", page, scaled=True), Pool("v", page, scaled=True)


def latent_widths(cfg) -> tuple:
    """Lane widths of the two parts of a latent (MLA) pool's ROW: the
    latent as it is (lanes ``0..R``), then the rope key padded with
    zeros to whole 128-lane tiles.  Their sum is the minor axis of the
    pool's one array (what is allocated and what the kernel DMAs)."""
    return cfg.kv_lora_rank, -(-cfg.qk_rope_head_dim // 128) * 128


def _latent_pools(cfg, page_size: int) -> tuple:
    """ONE array ``[P, R + 128]`` (``latent_widths``): the values are the
    latent lanes of the same row, so there is no second array (``v_pages`` is
    ``None``, and every path that moves pages as opaque buffers carries
    ``"v": None``)."""
    return (Pool("latent", (page_size, sum(latent_widths(cfg)))),)


def _indexed_pools(cfg, page_size: int) -> tuple:
    """The latent pool, and as ``v_pages`` the INDEX-KEY pool ``[P, Di]``
    under the same page ids and page tables: a page is a page of both."""
    return (*_latent_pools(cfg, page_size),
            Pool("index_keys", (page_size, cfg.index_head_dim)))


def _latent_geometry(cfg) -> tuple:
    """``(0, the width of a row of every array)``, which no K/V pool can
    match; nor can what the two-array layout the latent pool had before PR 40
    stated, ``(0, R)``: its pages are refused by that field's name instead of
    being misread, and a page of one pool is never read as a page of two."""
    return 0, sum(latent_widths(cfg)) + cfg.index_head_dim


def _latent_token_arrays(cfg) -> tuple:
    """The compressed latent ``(kv_lora_rank,)`` and the shared rope key
    ``(qk_rope_head_dim,)``: no head axis, no V."""
    return (cfg.kv_lora_rank,), (cfg.qk_rope_head_dim,)


def _indexed_token_arrays(cfg) -> tuple:
    """The index key rides behind the rope key: one fresh row a token for
    each of the two pools (``write_kv`` parts them)."""
    return (cfg.kv_lora_rank,), (cfg.qk_rope_head_dim + cfg.index_head_dim,)


def _kv_attend(rows, tables, backend, *, cfg, bucket=0, has_hist=True,
               cold=None, mesh=None):
    """The ragged paged op over the K and V pools (with int8 pools their
    scale pools).  ``cold`` (tiered KV residency) carries the staged
    cold-middle chunks plus each row's demoted token span: the op excludes
    the span from the hot gather and merges the chunks' online-softmax stats
    instead.  Prefill rows none of which has history read no page."""
    from helix_tpu.ops.paged import ragged_paged_attention

    if bucket and not has_hist:
        return None
    tkw = {}
    if cold is not None:
        (c_k, c_v, c_ks, c_vs, c_row, c_len, lo, hi) = cold
        tkw = dict(
            span_lo=lo, span_hi=hi, cold_k=c_k, cold_v=c_v,
            cold_row=c_row, cold_len=c_len,
            cold_k_scale=c_ks, cold_v_scale=c_vs,
        )

    def attend(q, k, v, caches, lyr):
        kp, vp, *scales = caches
        ks, vs = scales or (None, None)
        Bq, Sq, H, D = q.shape
        KVH = k.shape[-2]
        out = ragged_paged_attention(
            q.reshape(Bq * Sq, H, D),
            k.reshape(Bq * Sq, KVH, D),
            v.reshape(Bq * Sq, KVH, D),
            kp, vp, lyr, *rows, tables,
            backend=backend, mesh=mesh, max_q_len=Sq, k_scale=ks, v_scale=vs,
            **tkw,
        )
        return out.reshape(Bq, Sq, H, D)

    return attend


def _latent_attend(rows, tables, backend, *, cfg, bucket=0, has_hist=True,
                   cold=None, mesh=None):
    """Latent attention: ``k`` is the latent, ``v`` the rope key, no head
    axis, and the pool one array of their joined rows; the layer has scaled
    ``q`` already.  A row holds at most ``Sq`` fresh tokens (a decode slot's
    width, or the whole prefill bucket).  It has one kernel: a cold row is a
    row with no history."""
    from helix_tpu.ops.paged import mla_ragged_paged_attention

    def attend(q, k, v, caches, lyr):
        Bq, Sq, H, D = q.shape
        out = mla_ragged_paged_attention(
            q.reshape(Bq * Sq, H, D),
            k.reshape(Bq * Sq, k.shape[-1]),
            v.reshape(Bq * Sq, v.shape[-1]),
            caches[0], lyr, *rows, tables,
            backend=backend, max_q_len=Sq,
        )
        return out.reshape(Bq, Sq, H, out.shape[-1])

    return attend


def _indexed_attend(rows, tables, backend, *, cfg, bucket=0, has_hist=True,
                    cold=None, mesh=None):
    """Latent attention behind an indexer: ``v`` is ``[rope key | index
    key]``, ``qi`` the index queries, and each query attends the
    ``index_topk`` keys its index scores choose (``ops/dsa.py``).  A row with
    history chooses its keys, and so does a cold row in a bucket past
    ``index_topk``; a cold row of no more tokens than that attends all it has,
    on the latent kernel, and only caches its index keys."""
    from helix_tpu.ops.dsa import dsa_ragged_paged_attention

    if bucket and not has_hist and bucket <= cfg.index_topk:
        latent = _latent_attend(rows, tables, backend, cfg=cfg)
        return lambda q, k, v, qi, caches, lyr: latent(
            q, k, v[..., :cfg.qk_rope_head_dim], caches, lyr)

    def attend(q, k, v, qi, caches, lyr):
        Bq, Sq, H, D = q.shape
        out = dsa_ragged_paged_attention(
            q.reshape(Bq * Sq, H, D),
            k.reshape(Bq * Sq, k.shape[-1]),
            v.reshape(Bq * Sq, v.shape[-1]),
            qi.reshape(Bq * Sq, qi.shape[-1]),
            caches[0], caches[1], lyr, *rows, tables,
            index_heads=cfg.index_heads, topk=cfg.index_topk,
            backend=backend, max_q_len=Sq,
        )
        return out.reshape(Bq, Sq, H, out.shape[-1])

    return attend


def _kv_query_block(cfg, rung: int, rows: int) -> int:
    """The dense kernel's, as it sizes it from what the call sees: the bucket
    (``rung``: the segment's flat tokens and the bound on a row's), the rows
    it can hold and the query heads a kv head as the POOL holds them
    (``paged_query_block``: 128 tokens for a one-row 512-token chunk at a
    group of 8 or under)."""
    from helix_tpu.ops.paged_kernel import paged_query_block

    group = cfg.heads_of("attn") * cfg.kv_head_pack // cfg.num_kv_heads
    return paged_query_block(rung, group, rows, rung)


def _latent_query_block(cfg, rung: int, rows: int) -> int:
    """The latent kernel's: 8 tokens."""
    from helix_tpu.ops.paged_kernel import query_block

    return query_block(rung)


def _check_kv(cfg, tp, itemsize) -> None:
    from helix_tpu.ops.paged_kernel import check_geometry

    check_geometry(
        cfg.heads_of("attn") // tp, max(cfg.num_kv_heads // tp, 1),
        cfg.head_dim, itemsize)


def _check_latent(cfg, tp, itemsize) -> None:
    from helix_tpu.ops.mla_kernel import check_mla_geometry

    check_mla_geometry(
        cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim, itemsize)


def _check_indexed(cfg, tp, itemsize) -> None:
    from helix_tpu.ops.dsa_kernel import check_dsa_geometry

    _check_latent(cfg, tp, itemsize)
    check_dsa_geometry(
        cfg.index_heads, cfg.index_head_dim, cfg.num_heads,
        sum(latent_widths(cfg)))


def history_pages(rows, block: int, pos, n_extra, page_size: int) -> int:
    """History pages ONE paged layer's kernel walks in a launch, from the
    host's mirrors: over the live rows, the pages of a row's history
    (``ceil(hist / page)``: one DMA each) times the row's query blocks
    (each block walks the whole history again).  A prefill row is
    ``ceil(rem / block)`` blocks over its ``start`` tokens (``block``: the
    kind's ``query_block``, the kernel's own); a live state
    row (``pos`` its position) is one one-token block over its position,
    a page longer every ``page`` steps of the fused tail."""
    P = page_size
    pages = sum(-(-r.start // P) * -(-r.rem // block) for r in rows)
    for k in range(1 + int(n_extra)):
        pages += int((-(-(pos + k) // P)).sum())
    return pages


def _kv_account(cfg, cache_cfg, rows, pos, n_extra, rung=0,
                max_rows=0) -> dict:
    """K/V bytes of the pages the dense paged kernel walked (``history_pages``
    times a page's K and V over the full layers: what a roofline by hand
    divides the kernel's time by), and the programs that kernel ran for the
    launch's chunk rows over the full layers (a row's ``ceil(tokens /
    block)`` a layer: 4 a 512-token row at 128); rows with no history
    anywhere in the launch go to the packed flash kernel: the paged kernel
    runs no program."""
    block = _kv_query_block(cfg, rung, max_rows) if max_rows else 0
    pages = history_pages(rows, block, pos, n_extra, cache_cfg.page_size)
    return {
        "attn_page_bytes_read": pages * cache_cfg.page_bytes(cfg),
        "attn_query_blocks": cfg.num_attn_layers * any(
            r.start > 0 for r in rows) * sum(-(-r.rem // block) for r in rows),
    }


def _latent_account(cfg, cache_cfg, rows, pos, n_extra, rung=0,
                    max_rows=0) -> dict:
    """The history pages the latent kernel walked, one DMA each
    (``history_pages`` times the latent layers): the kernel's time over this
    is the cost of a page fetched (PERF.md section 5)."""
    block = _latent_query_block(cfg, rung, max_rows) if max_rows else 0
    return {"mla_page_fetches": cfg.num_attn_layers * history_pages(
        rows, block, pos, n_extra, cache_cfg.page_size)}


_INDEXED_COUNTS = ("keys_scored", "keys_selected", "rows_decode_sparse",
                   "rows_decode_all", "rows_chunk_sparse", "rows_chunk_all",
                   "index_bytes_read", "latent_rows_fetched", "select_bytes")


def _indexed_account(cfg, cache_cfg, rows, pos, n_extra, rung=0,
                     max_rows=0) -> dict:
    """The launch's account of a model with a sparse-attention indexer.  A
    query with ``n`` keys (its own position among them) scores ``n`` index
    keys a layer and attends ``min(n, topk)``: ``sparse`` past ``topk``, else
    ``all``.  A decode row fetches the latent rows it chose; a chunk row with
    history reads its history's latent rows ONCE (a dense copy for all
    its queries, which mask what they dropped); a cold row in a bucket
    of no more than ``topk`` tokens runs the latent kernel over its
    fresh tokens and scores nothing (and that kernel, which runs no row with
    history here, fetches no page).  ``index_bytes_read`` is what the
    device's gather moves out of the index-key pool (``ops/dsa.py::
    _gather_rows``): EVERY row of a segment's page table at the table's
    whole width, whatever the row holds (the scoring kernel then skips
    the key blocks past a row's history).  ``select_bytes`` is what a
    chunk row's CHOICE moves: the float32 scores of the flat axis'
    queries over the row's live key blocks and the fresh tokens, written
    once (the scoring kernel) and read twice (the threshold kernel, the
    attention kernel's mask): they follow the history, not the table;
    a decode row chooses by ``lax.top_k`` and moves none."""
    L, K = cfg.num_attn_layers, cfg.index_topk
    slots, table = cache_cfg.state_slots, cache_cfg.max_pages_per_seq
    row_bytes = (table * cache_cfg.page_size * cfg.index_head_dim
                 * jnp.dtype(cache_cfg.dtype).itemsize)
    inc = dict.fromkeys(_INDEXED_COUNTS, 0)
    for k in range(1 + int(n_extra)):
        n = pos + k + 1
        inc["keys_scored"] += int(n.sum())
        inc["keys_selected"] += int(np.minimum(n, K).sum())
        inc["rows_decode_sparse"] += int((n > K).sum())
        inc["rows_decode_all"] += int((n <= K).sum())
        inc["index_bytes_read"] += slots * row_bytes
        inc["latent_rows_fetched"] += int(np.minimum(n, K).sum())
    chooses = any(r.start > 0 for r in rows) or rung > K
    if max_rows and chooses:
        from helix_tpu.ops.dsa_kernel import SCORE_KEY_BLOCK

        inc["index_bytes_read"] += max_rows * row_bytes
        width = table * cache_cfg.page_size
        block = min(SCORE_KEY_BLOCK, -(-width // 128) * 128)
        inc["select_bytes"] = 3 * 4 * rung * (rung + sum(
            -(-r.start // block) * block for r in rows))
    for r in rows:
        n = np.arange(r.start + 1, r.start + r.rem + 1)
        mode = "sparse" if r.start + r.rem > K else "all"
        inc[f"rows_chunk_{mode}"] += 1
        if chooses:
            inc["keys_scored"] += int(n.sum())
            inc["keys_selected"] += int(np.minimum(n, K).sum())
            inc["latent_rows_fetched"] += int(n[-1])
    for key in inc:
        inc[key] *= L if not key.startswith("rows_") else 1
    return {**inc, "mla_page_fetches": 0}


_PAGE_FETCHES = Series(
    "helix_mla_page_fetches_total", "counter", "mla_page_fetches")
_LATENT = PageKind(
    refused_as="latent attention (MLA)",
    refusals=(
        ("multi_device",
         "the latent pool and its kernel are single-device: mesh {tp: 1}"),
        ("int8_kv", "the latent pool is bf16 or f32"),
        ("adapters", "no LoRA targets on MLA projections"),
        ("spec_decode", "untested on the latent kernel"),
        ("tiered", "tiered residency streams K/V chunks"),
    ),
    call_refusal=None,
    pools=_latent_pools,
    geometry=_latent_geometry,
    token_args=3,
    token_arrays=_latent_token_arrays,
    attend=_latent_attend,
    query_block=_latent_query_block,
    check_geometry=_check_latent,
    account=_latent_account,
    series=(_PAGE_FETCHES,),
    launch=(("mla_page_fetches", "mla_page_fetches"),),
    flight=(),
)

PAGE_KINDS = {
    "kv": PageKind(
        refused_as="K/V pages",
        refusals=(),
        call_refusal=None,
        pools=_kv_pools,
        geometry=lambda cfg: (cfg.num_kv_heads, cfg.head_dim),
        token_args=3,
        token_arrays=lambda cfg: ((cfg.num_kv_heads, cfg.head_dim),) * 2,
        attend=_kv_attend,
        query_block=_kv_query_block,
        check_geometry=_check_kv,
        account=_kv_account,
        series=(
            Series("helix_attn_page_bytes_read_total", "counter",
                   "attn_page_bytes_read"),
            Series("helix_attn_query_blocks_total", "counter",
                   "attn_query_blocks"),
        ),
        launch=(("attn_page_bytes", "attn_page_bytes_read"),
                ("attn_query_blocks", "attn_query_blocks")),
        flight=(("attn_page_bytes_read", "attn_page_bytes_read"),),
    ),
    "latent": _LATENT,
    "latent_indexed": PageKind(
        refused_as="a sparse-attention indexer (an index-key pool beside the "
                   "latent pool)",
        refusals=(
            ("host_tier",
             "the host tier moves pages of the latent pool; an index-key "
             "pool beside the latent pool is not carried there"),
        ),
        call_refusal="a page's contents leave the device as the latent "
                     "pool's alone; an index-key pool beside the latent pool "
                     "is not carried there",
        base=_LATENT,
        pools=_indexed_pools,
        geometry=_latent_geometry,
        token_args=4,
        token_arrays=_indexed_token_arrays,
        attend=_indexed_attend,
        query_block=_latent_query_block,
        check_geometry=_check_indexed,
        account=_indexed_account,
        series=(
            # from the host's account of the launches, times the latent
            # layers: index keys scored and keys then attended (their ratio
            # is the chosen share), rows by kind and by whether they were
            # past ``index_topk`` keys, the bytes the gather moves out of the
            # index-key pool, the latent rows fetched, and the score bytes a
            # chunk row's choice moves
            Series("helix_dsa_keys_scored_total", "counter", "keys_scored"),
            Series("helix_dsa_keys_selected_total", "counter",
                   "keys_selected"),
            *(Series("helix_dsa_rows_total", "counter", f"rows_{kind}_{mode}",
                     (("kind", kind), ("mode", mode)))
              for kind in ("decode", "chunk") for mode in ("sparse", "all")),
            Series("helix_dsa_index_bytes_read_total", "counter",
                   "index_bytes_read"),
            Series("helix_dsa_latent_rows_fetched_total", "counter",
                   "latent_rows_fetched"),
            Series("helix_dsa_select_bytes_total", "counter", "select_bytes"),
            # the index-key pool's bytes beside the latent pool's
            Series("helix_dsa_index_pool_bytes", "gauge",
                   "index_keys_pool_bytes"),
            _PAGE_FETCHES,
        ),
        launch=tuple(("dsa_" + key, key) for key in _INDEXED_COUNTS),
    ),
}


def flight_fields(kinds: tuple, values: dict, since: dict) -> dict:
    """The flight record's fields of EVERY kind, of state and of page, for a
    step of an engine whose layers are of ``kinds`` (the records,
    ``Engine.kinds``): ``values`` the engine's ``mixer_values()`` and
    ``mixer_gauges()`` after the step, ``since`` its ``mixer_counts`` before
    it.  A count reads what the step added, a level as it stands, a field of
    a kind the model has not 0."""
    out = {}
    for m in (*STATE_MIXERS.values(), *PAGE_KINDS.values()):
        for field, key in m.flight:
            if m not in kinds:
                out[field] = 0
            elif key in since:
                out[field] = values[key] - since[key]
            else:
                out[field] = values[key]
    return out
