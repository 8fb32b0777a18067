#!/usr/bin/env python
"""Time bare fused decode windows: device time vs wall.

Isolates: (a) the decode_fn call itself (device-resident args, donated),
(b) the [n, B] token fetch, (c) engine host bookkeeping.
"""

import sys
import time

sys.path.insert(0, ".")

import jax
import numpy as np

from helix_tpu.device.compile_cache import configure_compile_cache

configure_compile_cache()

from helix_tpu.engine.engine import Engine, EngineConfig, Request
from helix_tpu.engine.sampling import SamplingParams
from helix_tpu.models.common import LLAMA3_8B

import importlib.util
spec = importlib.util.spec_from_file_location("benchmod", "bench.py")


def build_params(cfg):
    import jax.numpy as jnp
    L, E, H, KVH, D, F, V = (
        cfg.num_layers, cfg.hidden_size, cfg.num_heads,
        cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size,
        cfg.vocab_size,
    )

    def qw(shape):
        n = shape[-1]
        w = (
            jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1) % 13
            - 6
        ).astype(jnp.int8)
        scale_shape = (shape[0], 1, n) if len(shape) == 3 else (1, n)
        return {"weight": w,
                "scale": jnp.full(scale_shape, 0.01, jnp.float32)}

    @jax.jit
    def build():
        return {
            "embed": {
                "weight": (
                    jax.lax.broadcasted_iota(jnp.int32, (V, E), 1) % 13 - 6
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

    p = build()
    jax.block_until_ready(p)
    return p


def main():
    cfg = LLAMA3_8B
    params = build_params(cfg)
    batch = 32
    eng = Engine(
        cfg, params,
        EngineConfig(
            max_decode_batch=batch, page_size=16, num_pages=2048,
            max_pages_per_seq=64, max_prefill_len=512,
            decode_steps_per_sync=16,
        ),
    )
    sampling = SamplingParams(temperature=0.0, max_tokens=1024)
    prompts = [
        [(7 * i + j) % 1000 + 1 for j in range(128)] for i in range(batch)
    ]
    for i, p in enumerate(prompts):
        eng.add_request(Request(id=f"r{i}", prompt_tokens=list(p),
                                sampling=sampling))
    for _ in range(3):
        eng.step()   # prefill everything, warm the window fns

    def window16():
        # the unified ragged step: zero drafts + a 15-step fused tail is
        # exactly the old 16-step decode window, one compiled shape
        return eng._ragged_step(
            draft_len=eng._zero_rows, n_extra=15,
        )

    # warm this exact shape
    _, toks, _, extra, _ = window16()
    _ = np.asarray(extra)

    # (a) bare window calls, sync only at the end of the run
    t0 = time.perf_counter()
    N = 5
    for _ in range(N):
        _, toks, _, extra, _ = window16()
    jax.block_until_ready(extra)
    dt = (time.perf_counter() - t0) / N
    print(f"bare 16-step window (pipelined): {dt*1000:7.1f} ms "
          f"-> {16*batch/dt:6.0f} tok/s")

    # (b) window + token fetch each time (the engine's actual pattern)
    t0 = time.perf_counter()
    for _ in range(N):
        _, toks, _, extra, _ = window16()
        _ = np.asarray(toks)
        _ = np.asarray(extra)
    dt = (time.perf_counter() - t0) / N
    print(f"window + np.asarray fetch:       {dt*1000:7.1f} ms "
          f"-> {16*batch/dt:6.0f} tok/s")

    # (c) full engine steps
    t0 = time.perf_counter()
    n_before = sum(len(r.output_tokens) for r in eng.slots if r)
    for _ in range(N):
        eng.step()
    n_after = sum(len(r.output_tokens) for r in eng.slots if r)
    dt = (time.perf_counter() - t0) / N
    print(f"full eng.step():                 {dt*1000:7.1f} ms "
          f"-> {(n_after-n_before)/(N*dt)*N:6.0f} tok/s")


if __name__ == "__main__":
    main()
