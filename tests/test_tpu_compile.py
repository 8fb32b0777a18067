"""Compile the main path's kernels for a DESCRIBED TPU v5e, without the chip.

The only place tests describe the chip.  The TPU's compiler is installed in
the sandbox and compiles for a topology that is described, not attached
(``on-chip-measurement`` guide, section 2): what Mosaic refuses here it
refuses on the chip, which interpret-mode parity tests cannot see (tiling,
slices, VMEM, partitioning).  Nothing runs, so these say nothing about
results or times; ``chip_smoke.py`` does that on the chip.

Rules this file keeps (or the whole suite counts 0 under xdist): the
topology is described inside a fixture, never while a module is imported,
never in a ``skipif`` or a ``parametrize`` argument; the fixtures live here
and are not ``autouse``; every compile happens in the test's own process;
all such tests stay in this one file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

GEOMETRY = {  # (query heads, kv heads, head width, layers)
    "qwen2-7b": (28, 4, 128, 28),
    "llama3-8b": (32, 8, 128, 32),
}
PAGE, PAGES, MAX_PAGES = 16, 2048, 64
DECODE_BATCH, PREFILL_LEN = 32, 512   # profiles/v5e1-qwen2-7b.yaml


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to a persistent cache
    # but never read back without the chip: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _ragged_args(geometry, kv, shape, sharding, heads=P(), pool=P(),
                 scales=P()):
    """ShapeDtypeStructs of one ragged-op call.  ``sharding(spec)`` places
    an argument; the specs only matter under a mesh."""
    H, KVH, D, L = GEOMETRY[geometry]
    T, R = (DECODE_BATCH, DECODE_BATCH) if shape == "decode" else (
        PREFILL_LEN, 1)

    def S(shp, dt, spec=P()):
        return jax.ShapeDtypeStruct(shp, dt, sharding=sharding(spec))

    pool_dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    args = [
        S((T, H, D), jnp.bfloat16, heads),
        S((T, KVH, D), jnp.bfloat16, heads),
        S((T, KVH, D), jnp.bfloat16, heads),
        S((L, PAGES, PAGE, KVH, D), pool_dt, pool),
        S((L, PAGES, PAGE, KVH, D), pool_dt, pool),
        S((), jnp.int32),
        S((R,), jnp.int32), S((R,), jnp.int32), S((R,), jnp.int32),
        S((R, MAX_PAGES), jnp.int32),
    ]
    if kv == "int8":
        args += [S((L, PAGES, KVH * PAGE), jnp.float32, scales)] * 2
    return args


def _compile_ragged(args, **kw):
    from helix_tpu.ops.paged import ragged_paged_attention

    def op(*a):
        ks, vs = a[10:] if len(a) > 10 else (None, None)
        return ragged_paged_attention(
            *a[:10], backend="pallas", k_scale=ks, v_scale=vs, **kw
        )

    return jax.jit(op).lower(*args).compile()


@pytest.mark.parametrize("shape", ["decode", "prefill_with_history"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
def test_ragged_kernel_compiles(one_chip, geometry, kv, shape):
    compiled = _compile_ragged(
        _ragged_args(geometry, kv, shape, lambda spec: one_chip)
    )
    assert compiled.memory_analysis() is not None


def test_decode_kernel_is_in_the_compiled_text(one_chip):
    compiled = _compile_ragged(
        _ragged_args("qwen2-7b", "bf16", "decode", lambda spec: one_chip)
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
def test_flash_attention_compiles(one_chip, geometry):
    from helix_tpu.ops.attention import attention

    H, KVH, D, _ = GEOMETRY[geometry]

    def S(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    q = S((1, PREFILL_LEN, H, D), jnp.bfloat16)
    kv = S((1, PREFILL_LEN, KVH, D), jnp.bfloat16)
    ids = S((1, PREFILL_LEN), jnp.int32)

    def op(q, k, v, pos, seg):
        return attention(
            q, k, v, causal=True, q_positions=pos, kv_positions=pos,
            q_segment_ids=seg, kv_segment_ids=seg, backend="pallas",
        )

    compiled = jax.jit(op).lower(q, kv, kv, ids, ids).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_head_width_96_is_a_typed_error(one_chip):
    """Phi-3-mini (32/32 heads of 96): the kernel has no lowering, and says
    so by name instead of handing Mosaic a shape cast it cannot do."""
    from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry

    GEOMETRY["phi3-mini"] = (32, 32, 96, 32)
    try:
        args = _ragged_args("phi3-mini", "bf16", "decode",
                            lambda spec: one_chip)
    finally:
        del GEOMETRY["phi3-mini"]
    with pytest.raises(UnsupportedKernelGeometry, match="width 96"):
        _compile_ragged(args)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_ragged_kernel_compiles_over_a_tp_mesh(topo, kv):
    """Heads sharded four ways (Llama-3-8B: 8 query / 2 kv heads a chip, so
    int8 KV's 4-head sublane pack does not fill and is a typed error): XLA
    cannot partition a Mosaic kernel, the dispatcher's shard_map does."""
    from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry

    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("dp", "tp"))
    args = _ragged_args(
        "llama3-8b", kv, "decode",
        lambda spec: NamedSharding(mesh, spec),
        heads=P(None, "tp", None),
        pool=P(None, None, None, "tp", None),
        scales=P(None, None, "tp"),
    )
    if kv == "int8":
        with pytest.raises(UnsupportedKernelGeometry, match="sublane pack"):
            _compile_ragged(args, mesh=mesh)
        return
    text = _compile_ragged(args, mesh=mesh).as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text   # nothing crosses chips: heads only


def test_one_kv_head_per_chip_is_a_typed_error():
    """Qwen2-7B at tp=4 leaves one bf16 kv head a chip: the pool's minor
    pair is padded in HBM and Mosaic refuses the page slice."""
    from helix_tpu.ops.paged_kernel import (
        UnsupportedKernelGeometry,
        check_geometry,
    )

    check_geometry(28, 4, 128, 2)          # tp=1
    check_geometry(14, 2, 128, 2)          # tp=2
    with pytest.raises(UnsupportedKernelGeometry, match="sublane pack"):
        check_geometry(7, 1, 128, 2)       # tp=4
