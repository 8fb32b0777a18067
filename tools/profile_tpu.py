#!/usr/bin/env python
"""Profile the serving hot path on the real chip: where do bench.py's
milliseconds actually go?  Times each phase separately:

- host<->device round-trip
- one packed-prefill call (512-token bucket)
- one fused decode window (n_steps x full batch)
- a full bench-shaped workload with a per-step timeline

Usage: python tools/profile_tpu.py [--steps N]
"""

import argparse
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

from helix_tpu.device.compile_cache import configure_compile_cache

configure_compile_cache()


def t(fn, n=5):
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--window", type=int, default=16)
    args = ap.parse_args()

    from helix_tpu.engine.engine import Engine, EngineConfig, Request
    from helix_tpu.engine.sampling import SamplingParams
    from helix_tpu.models.common import LLAMA3_8B, ModelConfig

    dev = jax.devices()[0]
    print(f"platform={dev.platform} device={dev}", file=sys.stderr)
    on_tpu = dev.platform == "tpu"

    # host<->device round trip: tiny transfer each way
    x = jnp.zeros((8,), jnp.int32)
    jax.block_until_ready(x)
    d = t(lambda: jax.device_get(x), 10)
    print(f"device_get(32B) round-trip: {d*1000:.1f} ms")
    small = jax.jit(lambda a: a + 1)
    jax.block_until_ready(small(x))
    d = t(lambda: jax.block_until_ready(small(x)), 10)
    print(f"trivial jit dispatch+sync:  {d*1000:.1f} ms")

    if on_tpu:
        cfg = LLAMA3_8B
        num_pages = 2048
        import importlib
        bench = importlib.import_module("bench")
        # reuse bench's on-device int8 weight builder
        sys.argv = [sys.argv[0]]
        L, E, H, KVH, D, F, V = (
            cfg.num_layers, cfg.hidden_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size,
            cfg.vocab_size,
        )

        def qw(shape):
            n = shape[-1]
            w = (
                jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
                % 13 - 6
            ).astype(jnp.int8)
            scale_shape = (shape[0], 1, n) if len(shape) == 3 else (1, n)
            return {
                "weight": w,
                "scale": jnp.full(scale_shape, 0.01, jnp.float32),
            }

        @jax.jit
        def build():
            return {
                "embed": {
                    "weight": (
                        jax.lax.broadcasted_iota(jnp.int32, (V, E), 1) % 13
                        - 6
                    ).astype(jnp.int8),
                    "embed_scale": jnp.full((V, 1), 0.01, jnp.float32),
                },
                "layers": {
                    "attn_norm": {"weight": jnp.ones((L, E), jnp.bfloat16)},
                    "mlp_norm": {"weight": jnp.ones((L, E), jnp.bfloat16)},
                    "wq": qw((L, E, H * D)),
                    "wk": qw((L, E, KVH * D)),
                    "wv": qw((L, E, KVH * D)),
                    "wo": qw((L, H * D, E)),
                    "w_gate": qw((L, E, F)),
                    "w_up": qw((L, E, F)),
                    "w_down": qw((L, F, E)),
                },
                "final_norm": {"weight": jnp.ones((E,), jnp.bfloat16)},
                "lm_head": qw((E, V)),
            }

        params = build()
        jax.block_until_ready(params)
    else:
        from helix_tpu.models.llama import init_params
        cfg = ModelConfig.tiny(dtype="float32")
        num_pages = 64
        params = init_params(cfg, jax.random.PRNGKey(0))

    batch = args.batch if on_tpu else 2
    prompt_len = 128 if on_tpu else 8
    gen_len = 128 if on_tpu else 8

    eng = Engine(
        cfg, params,
        EngineConfig(
            max_decode_batch=batch,
            page_size=16,
            num_pages=num_pages,
            max_pages_per_seq=64,
            max_prefill_len=512 if on_tpu else 32,
            decode_steps_per_sync=args.window if on_tpu else 1,
        ),
    )

    sampling = SamplingParams(temperature=0.0, max_tokens=gen_len)
    prompts = [
        [(7 * i + j) % (cfg.vocab_size - 2) + 1 for j in range(prompt_len)]
        for i in range(batch)
    ]

    # --- timeline of a bench-shaped workload --------------------------
    def run(tag):
        reqs = [
            Request(id=f"{tag}{i}", prompt_tokens=list(p), sampling=sampling)
            for i, p in enumerate(prompts)
        ]
        for r in reqs:
            eng.add_request(r)
        events = []
        t0 = time.perf_counter()
        while eng.has_work():
            s0 = time.perf_counter()
            before = sum(len(r.output_tokens) for r in reqs)
            eng.step()
            after = sum(len(r.output_tokens) for r in reqs)
            events.append((time.perf_counter() - s0, after - before))
        dt = time.perf_counter() - t0
        return events, dt, reqs

    run("w")  # warmup: compile everything
    events, dt, reqs = run("m")
    total = sum(len(r.output_tokens) for r in reqs)
    print(f"\nworkload: bs={batch} prompt={prompt_len} gen={gen_len}")
    print(f"total {total} tokens in {dt:.2f}s = {total/dt:.1f} tok/s")
    print(f"{len(events)} engine steps; slowest 12:")
    for ms, toks in sorted(events, reverse=True)[:12]:
        print(f"  {ms*1000:8.1f} ms  -> {toks} tokens")
    zero = [e for e in events if e[1] == 0]
    print(f"steps emitting 0 tokens: {len(zero)}  "
          f"({sum(e[0] for e in zero)*1000:.0f} ms total)")
    prefill_ms = sum(e[0] for e in events if e[1] <= batch and e[1] > 0
                     and events.index(e) < len(events) // 2)
    # decode steady state: steps emitting ~batch*window tokens
    big = [e for e in events if e[1] >= batch * max(1, args.window) // 2]
    if big:
        per = sum(e[0] for e in big) / len(big)
        toks = sum(e[1] for e in big) / len(big)
        print(f"steady decode windows: {len(big)} x {per*1000:.1f} ms "
              f"emitting {toks:.0f} tokens each = {toks/per:.0f} tok/s")


if __name__ == "__main__":
    main()
