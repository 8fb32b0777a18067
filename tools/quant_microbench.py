#!/usr/bin/env python
"""Microbench int8 weight-only matmul formulations on the chip.

Decode is weight-streaming-bound: the right formulation reads int8 from
HBM and dequantizes in VMEM.  The wrong one materializes a bf16/f32 copy
in HBM (3x traffic).  Times each candidate on the bench shapes.
"""

import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from helix_tpu.device.compile_cache import configure_compile_cache

configure_compile_cache()


def timeit(fn, *args, n=20):
    r = fn(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n


def main():
    B = 32
    E, F = 4096, 14336
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, E), jnp.bfloat16)
    w8 = jax.random.randint(key, (E, F), -127, 127, jnp.int8)
    wbf = w8.astype(jnp.bfloat16)
    scale = jnp.full((1, F), 0.01, jnp.float32)
    bytes_w8 = E * F
    bytes_bf = E * F * 2

    @jax.jit
    def mm_bf16(x, w):
        return jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.bfloat16)

    @jax.jit
    def mm_dequant_f32pref(x, w, s):
        out = jax.lax.dot_general(
            x, w.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (out * s).astype(jnp.bfloat16)

    @jax.jit
    def mm_dequant_bf16pref(x, w, s):
        out = jax.lax.dot_general(
            x, w.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.bfloat16,
        )
        return (out * s).astype(jnp.bfloat16)

    @jax.jit
    def mm_int8_direct(x, w, s):
        # mixed int8 rhs without explicit cast
        out = jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (out * s).astype(jnp.bfloat16)

    for name, fn, args, nbytes in [
        ("bf16 w (baseline)", mm_bf16, (x, wbf), bytes_bf),
        ("int8 cast->bf16, f32 acc", mm_dequant_f32pref, (x, w8, scale),
         bytes_w8),
        ("int8 cast->bf16, bf16 acc", mm_dequant_bf16pref, (x, w8, scale),
         bytes_w8),
        ("int8 direct mixed dot", mm_int8_direct, (x, w8, scale),
         bytes_w8),
    ]:
        try:
            dt = timeit(fn, *args)
            gbs = nbytes / dt / 1e9
            print(f"{name:28s}: {dt*1e6:8.0f} us  "
                  f"({gbs:6.0f} GB/s effective weight stream)")
        except Exception as e:  # noqa: BLE001
            print(f"{name:28s}: FAILED {type(e).__name__}: {e}")

    # stacked-layer scan variant: is dynamic-slice-from-stacked the issue?
    L = 8
    w8L = jax.random.randint(key, (L, E, F), -127, 127, jnp.int8)
    sL = jnp.full((L, 1, F), 0.01, jnp.float32)

    @jax.jit
    def scan_stacked(x, wL, sL):
        def body(h, ws):
            w, s = ws
            out = jax.lax.dot_general(
                h, w.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            h2 = (out * s).astype(jnp.bfloat16)
            return h2[:, :E], None

        h, _ = jax.lax.scan(body, x, (wL, sL))
        return h

    dt = timeit(scan_stacked, x, w8L, sL, n=5)
    per = dt / L
    print(f"{'scan over stacked int8':28s}: {per*1e6:8.0f} us/layer "
          f"({bytes_w8/per/1e9:6.0f} GB/s)")


if __name__ == "__main__":
    main()
