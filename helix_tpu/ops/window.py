"""Sliding-window attention over a per-sequence RING of K/V.

A window layer's query at position ``i`` sees keys ``i - W < j <= i`` (the
query's own among them), so all it ever needs of a sequence is its last ``W``
tokens' K and V.  They live in the state pool, not in pages: two rings
``[window layers, slots, W, kv heads, head_dim]``, a token at position ``p``
in ring row ``p mod W``, 2 * W * kv heads * head_dim values a layer and slot
whatever the sequence's length.

- ``window_attention`` — the dispatcher, under ``ops/paged.py``'s ragged
  contract: queries on a flat token axis carved into rows (``t0`` / ``q_len``),
  ``hist[r]`` tokens behind row ``r`` and its ring in slot ``slots[r]``.  A
  decode step is R one-token rows; a chunk that continues a prompt is one row
  of up to a bucket of tokens; a row with ``hist`` 0 reads no ring.  Fresh K/V
  are attended raw, beside the ring AS IT STOOD BEFORE the call: persisting
  them is ``write_ring``, after the call.
- ``ring_positions`` — which position each ring row holds for a sequence with
  ``hist`` tokens behind it, by arithmetic alone: a ring is never cleared, and
  a row that holds nothing of this sequence (or a token that has left the
  window) is masked BY POSITION, so what a finished sequence left in a slot
  never reaches the next one.
- ``window_attention_reference`` — the XLA oracle: gathers every row's ring
  and runs the plain-softmax ``mha_reference`` with positions, segments and
  the window.
- ``window_attention_tpu`` (``ops/window_kernel.py``) — the Pallas kernel:
  a row's ring comes in ONE contiguous DMA a pool (``[W, kv heads,
  head_dim]``: 1 MB at 512 x 8 x 128 in bf16), not page by page, and stays in
  VMEM for every query block of its row.  A decode row is a one-token block
  that walks the ring; a chunk row is long blocks (128 tokens at a query
  group of 8) that score one kv head at a time against the ``W + block`` keys
  they can see, the row's keys laid out head-major and by position once.
"""

from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp

from helix_tpu.ops.attention import mha_reference, resolve_backend
from helix_tpu.ops.paged import _row_of_tokens


def ring_positions(hist, window: int):
    """``[..., W]``: the position ring row ``j`` holds for a sequence with
    ``hist [...]`` tokens written, ``hist - 1 - ((hist - 1 - j) mod W)``: the
    last position before ``hist`` that is ``j`` mod ``W``.  NEGATIVE where the
    sequence has not written the row (``j >= hist``): whatever lies there is
    another sequence's."""
    j = jnp.arange(window, dtype=jnp.int32)
    last = hist[..., None].astype(jnp.int32) - 1
    return last - jnp.mod(last - j, window)


def window_attention_reference(
    q,            # [T, H, D] flat fresh queries
    k_new,        # [T, KVH, D] fresh K/V, attended raw
    v_new,
    k_ring,       # [L, slots, W, KVH, D] — FULL pool
    v_ring,
    layer,        # scalar int32 — which window layer's rings
    t0,           # [R] int32 — row r's first flat token (ascending)
    q_len,        # [R] int32 — row r's fresh-token count (0 = unused)
    hist,         # [R] int32 — tokens behind row r
    slots,        # [R] int32 — row r's slot in the pool
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
):
    """XLA oracle: one segment-masked kv axis (R rings + the fresh tokens),
    each ring row at the position it holds (``ring_positions``; rows that
    hold nothing of the sequence in segment 0), under the causal and the
    window mask.  ``window``: the mask's width if not the ring's length (a
    control's: one more lets in the row that is about to be overwritten)."""
    T, H, D = q.shape
    _, nslots, W, KVH, _ = k_ring.shape
    R = t0.shape[0]
    row, q_off = _row_of_tokens(t0, q_len, T)
    q_pos = jnp.where(row >= 0, hist[jnp.clip(row, 0)] + q_off, 0)
    at = jnp.clip(slots, 0, nslots - 1)
    kh = k_ring[layer][at].astype(q.dtype).reshape(1, R * W, KVH, D)
    vh = v_ring[layer][at].astype(q.dtype).reshape(1, R * W, KVH, D)
    pos_h = ring_positions(hist, W)                       # [R, W]
    seg_h = jnp.where(pos_h >= 0, jnp.arange(R)[:, None] + 1, 0)
    seg_fresh = jnp.where(row >= 0, row + 1, 0)
    out = mha_reference(
        q[None],
        jnp.concatenate([kh, k_new.astype(q.dtype)[None]], axis=1),
        jnp.concatenate([vh, v_new.astype(q.dtype)[None]], axis=1),
        causal=True,
        q_positions=q_pos[None],
        kv_positions=jnp.concatenate(
            [pos_h.reshape(1, R * W), q_pos[None]], axis=1),
        q_segment_ids=seg_fresh[None],
        kv_segment_ids=jnp.concatenate(
            [seg_h.reshape(1, R * W), seg_fresh[None]], axis=1),
        scale=scale,
        window=window or W,
    )
    return out[0]


def window_attention(
    q, k_new, v_new, k_ring, v_ring, layer, t0, q_len, hist, slots, *,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
    max_q_len: Optional[int] = None,
):
    """THE window-attention entry point over the rings; returns ``out [T,
    H, D]``.  The window is the ring's length.  The Pallas kernel on a TPU,
    the XLA oracle on a CPU or for ``backend="reference"``; on a TPU the
    kernel runs or the call raises.  ``max_q_len``: a static bound on a
    row's fresh tokens (1 for a decode step), which sizes the kernel's
    query blocks."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if resolve_backend(backend) == "pallas":
        from helix_tpu.ops.window_kernel import window_attention_tpu

        return window_attention_tpu(
            q, k_new, v_new, k_ring, v_ring, layer, t0, q_len, hist, slots,
            scale=scale, max_q_len=max_q_len)
    return window_attention_reference(
        q, k_new, v_new, k_ring, v_ring, layer, t0, q_len, hist, slots,
        scale=scale)


def write_ring(k_ring, v_ring, layer, k_new, v_new, t0, q_len, hist, slots):
    """The rows' fresh K/V ``[T, KVH, D]`` into their slots' rings at layer
    ``layer``, in place: the token at position ``p`` to ring row ``p mod W``.
    Of a row longer than the ring only its last ``W`` tokens land (the
    others would be overwritten by them); a row with no fresh token, a token
    outside every row and a slot index past the pool write nothing.

    One fused index into a ``[L * slots * W, KVH, D]`` view (a bitcast: the
    leading axes are contiguous): the update block is one token's ``[KVH,
    D]``, so the pool keeps its row-major layout (``engine/kv_cache.py::
    write_kv``)."""
    L, nslots, W, KVH, D = k_ring.shape
    T = k_new.shape[0]
    row, q_off = _row_of_tokens(t0, q_len, T)
    at = jnp.clip(row, 0)
    slot = slots[at]
    ok = (row >= 0) & (slot >= 0) & (slot < nslots) & (
        q_len[at] - q_off <= W)
    idx = jnp.where(
        ok, (layer * nslots + slot) * W + jnp.mod(hist[at] + q_off, W),
        L * nslots * W)

    def put(pool, new):
        flat = pool.reshape(L * nslots * W, KVH, D)
        return flat.at[idx].set(new.astype(pool.dtype), mode="drop").reshape(
            pool.shape)

    return put(k_ring, k_new), put(v_ring, v_new)
