#!/usr/bin/env python3
"""DeepSeek-V2-Lite on the chip, against its references (``chip_smoke.py``'s
sibling for the latent-attention, expert-layer path).

    python3 chip_smoke_deepseek.py [--seed N] [--layers 17] [--steps 32]

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


def phase_kernel(seed, rehearse):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from helix_tpu.models.moe import experts_pallas, experts_xla
    from helix_tpu.ops.grouped_matmul import row_tile, visit_plan
    from helix_tpu.ops.mla_kernel import mla_ragged_paged_attention_tpu
    from helix_tpu.ops.paged import (
        mla_ragged_paged_attention,
        mla_ragged_paged_attention_reference,
    )

    H, R, dr, P, L = 16, 512, 64, 16, 2
    N, maxP, B, S = (64, 8, 4, 32) if rehearse else (2048, 160, 64, 512)
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = jnp.float32 if rehearse else jnp.bfloat16
    c_pages = jax.random.normal(ks[0], (L, N, P, R), jnp.float32).astype(dt)
    r_pages = jnp.pad(
        jax.random.normal(ks[1], (L, N, P, dr), jnp.float32).astype(dt),
        ((0, 0),) * 3 + ((0, 128 - dr),))
    pages = rng.permutation(np.arange(1, N))
    shapes = {
        "decode": (B, np.arange(B), np.ones(B, int),
                   rng.integers(1, maxP * P - 1, size=B),
                   np.resize(pages, (B, maxP)), 1),
        "chunk_with_history": (S, np.zeros(1, int), np.array([S]),
                               np.array([(maxP * P) // 2 - 5]),
                               pages[:maxP][None], S),
        "packed_cold": (S, np.array([0, S // 4, S // 4 + 7]),
                        np.array([S // 4, 7, S // 2]), np.zeros(3, int),
                        np.zeros((3, maxP), int), S),
    }
    ok = True
    for name, (T, t0, q_len, hist, tables, mq) in shapes.items():
        q = (jax.random.normal(ks[2], (T, H, R + dr)) * 0.1).astype(dt)
        c_new = jax.random.normal(ks[3], (T, R)).astype(dt)
        r_new = jax.random.normal(ks[4], (T, dr)).astype(dt)
        args = (q, c_new, r_new, c_pages, r_pages, jnp.int32(1),
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
        in_row = np.zeros(T, bool)
        for s0, n in zip(t0, q_len):
            in_row[s0:s0 + n] = True
        got, want = (np.asarray(x, np.float32)[in_row] for x in (got, want))
        err = float(np.abs(got - want).max())
        good = bool(np.isfinite(got).all() and err <= TOL_BF16)
        ok &= good
        say(phase="kernel", op="mla_ragged_paged_attention",
            geometry=[H, R, dr], shape=name, tokens=T, max_abs_err=err,
            tol=TOL_BF16, ok=good)
    if not ok:
        fail("the latent kernel disagrees with its reference")
    ok = True
    X, E, F = (8, 256, 128) if rehearse else (64, 2048, 1408)
    stack = {
        name: {"weight": jnp.asarray(rng.integers(
                   -127, 128, (2, X, k, n), dtype=np.int8)),
               "scale": jnp.asarray(
                   rng.random((2, X, 1, n)) * 4e-4 + 1e-4, jnp.float32)}
        for name, (k, n) in (("w_gate", (E, F)), ("w_up", (E, F)),
                             ("w_down", (F, E)))}
    for name, rows, skew in (("decode", 6 * B, 8.0), ("chunk", 6 * S, 1.2)):
        sizes = rng.multinomial(rows - 7, rng.dirichlet(np.full(X, skew)))
        xs = jax.random.normal(ks[2], (rows, E)).astype(jnp.bfloat16)
        tm = row_tile(rows, X)
        gs = jnp.asarray(sizes, jnp.int32)
        got = experts_pallas(xs, visit_plan(gs, rows, tm), tm, stack, 1,
                             jax.nn.silu, rehearse)
        # each sorted row's group; the last 7 rows belong to none
        e_row = np.concatenate(
            [np.repeat(np.arange(X), sizes), np.full(7, X - 1)])
        want = experts_xla(
            xs, gs, jnp.asarray(e_row), stack, 1, jax.nn.silu)
        got, want = (np.asarray(x, np.float32)[:rows - 7]
                     for x in (got, want))
        err = float(np.abs(got - want).max() / want.std())
        good = bool(np.isfinite(got).all() and err <= TOL_BF16)
        ok &= good
        say(phase="kernel", op="grouped_matmul", geometry=[X, E, F],
            shape=name, rows=rows, row_tile=tm, busiest=int(sizes.max()),
            max_abs_err_over_std=err, tol=TOL_BF16, ok=good)
    if not ok:
        fail("the grouped expert product kernel disagrees with ragged_dot")


def phase_engine(seed, layers, steps, rehearse):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import reference_mla_moe_decoder as reference
    from helix_tpu.engine.engine import (
        Engine, EngineConfig, Request, SamplingParams,
    )
    from helix_tpu.models.common import DEEPSEEK_V2_LITE, ModelConfig
    from helix_tpu.models.llama import init_params

    with open(os.path.join(HERE, "benchmark", "configs",
                           "deepseek-v2-lite-int8.json")) as f:
        hf = json.load(f)
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
        hf = dict(hf, num_hidden_layers=3, num_attention_heads=4,
                  kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16, n_routed_experts=8, num_experts_per_tok=3)
        ecfg = EngineConfig(max_decode_batch=2, page_size=8, num_pages=64,
                            max_pages_per_seq=16, max_prefill_len=16,
                            attn_backend="reference")
        n_prompt, steps = 24, 4
    else:
        cfg = dataclasses.replace(DEEPSEEK_V2_LITE, num_layers=layers)
        hf = dict(hf, num_hidden_layers=layers)
        ecfg = EngineConfig(max_decode_batch=8, page_size=16, num_pages=512,
                            max_pages_per_seq=160, max_prefill_len=512)
        n_prompt = 600
    t = time.monotonic()
    params = init_params(cfg, jax.random.PRNGKey(seed),
                         int8=not rehearse)
    jax.block_until_ready(params)
    eng = Engine(cfg, params, ecfg)
    say(phase="engine", layers=cfg.num_layers, weights_s=round(
        time.monotonic() - t, 1), backend=eng._backend,
        grouped_backend=eng.grouped_backend)
    prompt = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, size=n_prompt).tolist()
    req = Request(id="smoke", prompt_tokens=prompt,
                  sampling=SamplingParams(max_tokens=steps + 4,
                                          temperature=1.0, seed=seed))
    eng.add_request(req)

    # The reference a layer at a time, jitted (eagerly its loop over 64
    # experts takes a minute a forward): one program a layer kind, the
    # layer's index dynamic, the sequence padded to a fixed length (causal:
    # what follows a position does not reach it).
    s_pad = -(-(n_prompt + steps + 8) // 64) * 64
    pos = jnp.arange(s_pad)
    inv_freq = jnp.asarray(reference.yarn_inv_freq(
        hf["qk_rope_head_dim"], hf["rope_theta"], hf["rope_scaling"]))
    faults_kw = {
        "none": {},
        "act_8bit": {"act": reference.round_to_8_bits},
        # calibration, not a fault: what bf16 activations alone read
        "act_bf16": {"act": lambda x: x.astype(jnp.bfloat16).astype(
            jnp.float32)},
        "dropped_expert": {"top_k": hf["num_experts_per_tok"] - 1},
    }

    @functools.partial(jax.jit, static_argnames=("fault",))
    def ref_layer(h, stack, i, fault):
        kw = {"act": lambda x: x, "top_k": None, **faults_kw[fault]}
        with jax.default_matmul_precision("highest"):
            return reference._layer(h, stack, i, hf, pos, inv_freq,
                                    kw["act"], kw["top_k"])

    @functools.partial(jax.jit, static_argnames=("fault",))
    def ref_ends(tokens, h, last, fault):
        act = faults_kw[fault].get("act", lambda x: x)
        with jax.default_matmul_precision("highest"):
            if h is None:
                return reference._f32(params["embed"])[tokens]
            x = act(reference.rms_norm(
                h[last], params["final_norm"]["weight"].astype(jnp.float32),
                hf["rms_norm_eps"]))
            return x @ reference._f32(params["lm_head"])

    def ref(seq, fault="none"):
        """The reference's logits at the last position of ``seq``."""
        n = len(seq)
        toks = jnp.asarray(list(seq) + [0] * (s_pad - n), jnp.int32)
        h = ref_ends(toks, None, 0, fault)
        for layer in range(cfg.num_layers):
            dense = layer < cfg.first_k_dense
            h = ref_layer(
                h, params["dense_layers" if dense else "layers"],
                jnp.int32(layer if dense else layer - cfg.first_k_dense),
                fault)
        return np.asarray(ref_ends(toks, h, n - 1, fault), np.float32)

    def rel_rms(got, want):
        return float(np.sqrt(np.mean((got - want) ** 2)) / want.std())

    rows, faults = [], []
    while eng.has_work() and len(rows) < steps:
        eng.step()
        if not req.output_tokens or req.slot is None or (
                eng.slots[req.slot] is not req):
            continue
        seq = prompt + req.output_tokens
        got = np.asarray(eng.next_token_logits()[req.slot], np.float32)
        want = ref(seq)
        row = {"step": len(rows), "tokens": len(seq),
               "rel_rms_err": rel_rms(got, want),
               "max_abs_err": float(np.abs(got - want).max()),
               "logit_std": float(want.std())}
        rows.append(row)
        say(phase="engine", **row)
        if len(rows) in (1, steps):
            # what two faults would read, on the same tokens
            for name in ("act_bf16", "act_8bit", "dropped_expert"):
                bad = ref(seq, name)
                faults.append({"step": row["step"], "fault": name,
                               "rel_rms_err": rel_rms(bad, want),
                               "max_abs_err": float(
                                   np.abs(bad - want).max())})
                say(phase="engine", **faults[-1])
    errs = sorted(r["rel_rms_err"] for r in rows)
    worst, median = errs[-1], errs[len(errs) // 2]
    least_fault = min(f["rel_rms_err"] for f in faults
                      if f["fault"] != "act_bf16")
    # every (token, choice) of every expert layer routed, none dropped:
    # the prompt's tokens and one decode step a token after the first
    forwards = n_prompt + len(req.output_tokens) - 1
    want_routed = forwards * cfg.num_experts_per_tok * cfg.num_moe_layers
    counted = (eng.moe_dropped_tokens == 0
               and eng.moe_routed_tokens == want_routed)
    ok = (len(rows) >= steps and median <= TOL_LOGITS < least_fault
          and worst <= TOL_LOGITS_WORST and counted)
    say(phase="engine", steps=len(rows), prompt_tokens=n_prompt,
        chunks=-(-n_prompt // ecfg.max_prefill_len),
        median_rel_rms_err=median, worst_rel_rms_err=worst,
        worst_max_abs_err=max(
            r["max_abs_err"] for r in rows), faults=faults,
        tol_median=TOL_LOGITS, tol_worst=TOL_LOGITS_WORST, moe_dropped=eng.moe_dropped_tokens,
        moe_routed=eng.moe_routed_tokens, moe_routed_expected=want_routed,
        experts_touched=eng.moe_experts_touched,
        tile_fill=eng.moe_tile_fill_ratio,
        load_max_ratio=eng.moe_expert_load_max_ratio, ok=ok)
    if not ok and not rehearse:
        fail("the engine and the reference part by more than the "
             "tolerance, the tolerance does not separate the two faults, "
             "or the routing count is off")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layers", type=int, default=17)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    device = _device_or_die(args.rehearse, 1)
    phase_kernel(args.seed, args.rehearse)
    phase_engine(args.seed, args.layers, args.steps, args.rehearse)
    if args.rehearse:
        say(ok=False, rehearsal=True, device=device)
        sys.exit(4)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
