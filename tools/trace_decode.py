#!/usr/bin/env python
"""Capture an XLA profiler trace of the fused decode window on the real
chip and print the top ops by self time (via xprof's op-stats converter).
"""

import glob
import os
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

from helix_tpu.device.compile_cache import configure_compile_cache

configure_compile_cache()

TRACE_DIR = "/tmp/helix_trace"


def main():
    from helix_tpu.engine.engine import Engine, EngineConfig, Request
    from helix_tpu.engine.sampling import SamplingParams
    from helix_tpu.models.common import LLAMA3_8B

    cfg = LLAMA3_8B
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
        return {
            "weight": w,
            "scale": jnp.full(scale_shape, 0.01, jnp.float32),
        }

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

    params = build()
    jax.block_until_ready(params)

    batch, prompt_len = 32, 128
    eng = Engine(
        cfg, params,
        EngineConfig(
            max_decode_batch=batch, page_size=16, num_pages=2048,
            max_pages_per_seq=64, max_prefill_len=512,
            decode_steps_per_sync=16,
        ),
    )
    sampling = SamplingParams(temperature=0.0, max_tokens=64)
    prompts = [
        [(7 * i + j) % (cfg.vocab_size - 2) + 1 for j in range(prompt_len)]
        for i in range(batch)
    ]
    for i, p in enumerate(prompts):
        eng.add_request(Request(id=f"r{i}", prompt_tokens=list(p),
                                sampling=sampling))
    # admit + prefill everything, get into steady decode
    for _ in range(3):
        eng.step()
    print("entering traced window", file=sys.stderr)
    os.makedirs(TRACE_DIR, exist_ok=True)
    with jax.profiler.trace(TRACE_DIR):
        t0 = time.perf_counter()
        eng.step()
        dt = time.perf_counter() - t0
    print(f"traced step: {dt*1000:.1f} ms", file=sys.stderr)
    while eng.has_work():
        eng.step()

    # ---- parse the xplane and print op stats ----
    files = glob.glob(f"{TRACE_DIR}/**/*.xplane.pb", recursive=True)
    print(f"xplane files: {files}", file=sys.stderr)
    if not files:
        return
    path = max(files, key=os.path.getmtime)
    try:
        from xprof.convert import raw_to_tool_data as rtd
        params2 = {"tqx": "out:csv;"}
        data, _ = rtd.xspace_to_tool_data([path], "op_profile", params2)
        print(data[:4000] if isinstance(data, (str, bytes)) else data)
    except Exception as e:  # noqa: BLE001
        print(f"op_profile failed: {e}", file=sys.stderr)
        try:
            from xprof.convert import raw_to_tool_data as rtd
            data, _ = rtd.xspace_to_tool_data(
                [path], "framework_op_stats", {"tqx": "out:csv;"}
            )
            out = data.decode() if isinstance(data, bytes) else str(data)
            lines = out.splitlines()
            print("\n".join(lines[:40]))
        except Exception as e2:  # noqa: BLE001
            print(f"framework_op_stats failed: {e2}", file=sys.stderr)


if __name__ == "__main__":
    main()
