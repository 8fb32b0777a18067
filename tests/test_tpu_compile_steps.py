"""Compile whole engine steps at published widths for a DESCRIBED TPU v5e,
without the chip: DeepSeek-V2-Lite (latent attention, experts), LFM2-8B-A1B
(conv layers), Brumby-14B (power retention), GigaChat3.5 (the gated delta
rule beside latent attention), Laguna-XS.2 (sliding-window layers),
Nemotron-3-Super (Mamba-2 layers, experts in a latent), Mellum2-12B-A2.5B
(rings of 1,024 over 4 kv heads, a page table 544 wide, 64 experts held) and
Qwen3-Next-80B-A3B (the WHOLE cut of twelve layers: the delta rule beside GQA
pages at 256 lanes a head, a page table 1,056 wide, 256 experts held).

The rules of ``test_tpu_compile.py`` hold here (its docstring); the fixtures
are ``tests/tpu_topology.py``'s.  Nothing runs, so these say nothing about
results or times; ``chip_smoke_deepseek.py`` does that on the chip.

ONE file, so one process compiles whole steps at a time (a compile is 2.7
cores wide for half a minute to a minute), and ``tests/conftest.py`` hands it
out first: by its few tests xdist would start it last.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_topology import one_chip, topo  # noqa: F401

PAGE = 16


@pytest.mark.parametrize("program", ["decode", "chunk_with_history"])
def test_expert_step_compiles_at_published_widths(one_chip, program):
    """A whole engine step of DeepSeek-V2-Lite (the dense layer and two
    expert layers of the seventeen, int8 weights, 64 slots) for the
    described chip: the latent kernel, the grouped expert product kernel
    with int8 weights as stored, the row scatter into the latent pool."""
    import dataclasses

    from helix_tpu.engine import engine as E
    from helix_tpu.engine.kv_cache import CacheConfig, PagedKVCache
    from helix_tpu.engine.sampling import SamplingState
    from helix_tpu.models.common import DEEPSEEK_V2_LITE
    from helix_tpu.models.llama import init_params

    cfg = dataclasses.replace(DEEPSEEK_V2_LITE, num_layers=3)
    B, max_pages, pages = 64, 160, 10240
    i32 = jnp.int32

    def S(shp, dt=i32):
        return jax.ShapeDtypeStruct(tuple(shp), dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), int8=True)))
    ks, = CacheConfig(num_pages=pages).page_shapes(cfg)
    assert ks == (3, 16, 512 + 128)
    cache = PagedKVCache(
        k_pages=S((ks[0], pages) + ks[1:], jnp.bfloat16), v_pages=None)

    def sampling(n):
        f32 = jnp.float32
        return SamplingState(
            temperature=S((n,), f32), top_p=S((n,), f32), top_k=S((n,)),
            presence=S((n,), f32), frequency=S((n,), f32))

    state = E.DecodeState(
        last_token=S((B,)), positions=S((B,)),
        page_tables=S((B, max_pages)), active=S((B,)),
        mrope_delta=S((B,)), keys=S((B, 2), jnp.uint32),
        token_counts=S((B, cfg.vocab_size)), adapter_slots=S((B,)),
        sampling=sampling(B))
    bucket, rows = (0, 0) if program == "decode" else (512, 1)
    pargs = () if not bucket else (
        *(S((1, bucket)) for _ in range(5)), S((rows,)), S((rows,)),
        S((rows,)), S((rows, max_pages)), S((rows,)), sampling(rows),
        S((rows, 2), jnp.uint32))
    fn = E._build_ragged_step_fn(
        cfg, PAGE, "pallas", None, bucket, bool(bucket), rows, 1, 7)
    compiled = fn.lower(
        params, cache, state, pargs, S((B, 0)), S((B,)), S(()), None
    ).compile()
    text = compiled.as_text()
    assert "mla_ragged_paged_attention_tpu" in text
    # the grouped expert product is this repo's kernel, by the name a trace
    # finds it by (benchmark/metrics/kernel.grouped_mm_share.json), and
    # XLA's ragged_dot kernel is gone from the step
    assert "grouped_matmul_tpu" in text
    assert "ragged-dot" not in text and "ragged_dot" not in text
    # the pool is updated in place: no pool-sized temporary
    pool_bytes = (ks[0] * pages * 16 * (512 + 128)) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


@pytest.mark.parametrize("program", ["decode", "chunk_with_history"])
def test_hybrid_step_compiles_at_published_widths(one_chip, program):
    """A whole engine step of LFM2-8B-A1B cut to seven layers that hold all
    three kinds (conv+dense, then attention+experts and two conv+experts
    twice: a repeated group; int8 weights, 64 slots) for the described chip: the conv operator over the flat
    ragged axis with the state pool in the carry, the paged kernel at head
    width 64, the grouped expert product, both pools updated in place."""
    import dataclasses

    from helix_tpu.engine import engine as E
    from helix_tpu.engine.kv_cache import CacheConfig, PagedKVCache
    from helix_tpu.engine.sampling import SamplingState
    from helix_tpu.models.common import LFM2_8B_A1B
    from helix_tpu.models.llama import init_params

    cfg = dataclasses.replace(
        LFM2_8B_A1B, num_layers=7, first_k_dense=1,
        layer_types=("conv",) + ("attn", "conv", "conv") * 2)
    assert [g.reps for g in cfg.layer_runs()] == [1, 2]
    B, max_pages, pages = 64, 160, 10240
    i32 = jnp.int32

    def S(shp, dt=i32):
        return jax.ShapeDtypeStruct(tuple(shp), dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), int8=True)))
    cc = CacheConfig(num_pages=pages, state_slots=B)
    ks, vs = cc.page_shapes(cfg)
    assert ks == (2, 16, 4, 128) and cc.state_shape(cfg) == (5, B, 2, 2048)
    cache = PagedKVCache(
        k_pages=S((ks[0], pages) + ks[1:], jnp.bfloat16),
        v_pages=S((vs[0], pages) + vs[1:], jnp.bfloat16),
        state=S(cc.state_shape(cfg), jnp.bfloat16))

    def sampling(n):
        f32 = jnp.float32
        return SamplingState(
            temperature=S((n,), f32), top_p=S((n,), f32), top_k=S((n,)),
            presence=S((n,), f32), frequency=S((n,), f32))

    state = E.DecodeState(
        last_token=S((B,)), positions=S((B,)),
        page_tables=S((B, max_pages)), active=S((B,)),
        mrope_delta=S((B,)), keys=S((B, 2), jnp.uint32),
        token_counts=S((B, cfg.vocab_size)), adapter_slots=S((B,)),
        sampling=sampling(B))
    bucket, rows = (0, 0) if program == "decode" else (512, 1)
    pargs = () if not bucket else (
        *(S((1, bucket)) for _ in range(5)), S((rows,)), S((rows,)),
        S((rows,)), S((rows, max_pages)), S((rows,)), sampling(rows),
        S((rows, 2), jnp.uint32), S((rows,)), S((rows,)))
    fn = E._build_ragged_step_fn(
        cfg, PAGE, "pallas", None, bucket, bool(bucket), rows, 1, 7)
    compiled = fn.lower(
        params, cache, state, pargs, S((B, 0)), S((B,)), S(()), None
    ).compile()
    text = compiled.as_text()
    assert "ragged_paged_attention_tpu" in text
    assert "grouped_matmul_tpu" in text
    assert "ragged-dot" not in text and "ragged_dot" not in text
    # both pools are updated in place: no pool-sized temporary
    assert compiled.memory_analysis().temp_size_in_bytes < (
        pages * cc.page_bytes(cfg)) // 2


@pytest.mark.parametrize("program", ["decode", "chunk_with_history"])
def test_retention_step_compiles_at_published_widths(one_chip, program):
    """A whole engine step of Brumby-14B cut to two layers (int8 weights, 24
    slots) for the described chip: the retention decode kernel over the
    state pool in the carry, the chunked form a row at a time, a page pool
    of no bytes, and both state arrays updated in place."""
    import dataclasses

    from helix_tpu.engine import engine as E
    from helix_tpu.engine.kv_cache import CacheConfig, PagedKVCache
    from helix_tpu.engine.sampling import SamplingState
    from helix_tpu.models.common import BRUMBY_14B
    from helix_tpu.models.llama import init_params

    cfg = dataclasses.replace(
        BRUMBY_14B, num_layers=2, layer_types=("retention",) * 2)
    B, max_pages, pages = 24, 160, 4096
    i32 = jnp.int32

    def S(shp, dt=i32):
        return jax.ShapeDtypeStruct(tuple(shp), dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), int8=True)))
    cc = CacheConfig(num_pages=pages, state_slots=B,
                     max_pages_per_seq=max_pages)
    ks, vs = cc.page_shapes(cfg)
    assert ks[0] == 0 and cc.page_bytes(cfg) == 0
    assert cc.state_shape(cfg) == (2, B, 8, 8704, 128)
    cache = PagedKVCache(
        k_pages=S((ks[0], pages) + ks[1:], jnp.bfloat16),
        v_pages=S((vs[0], pages) + vs[1:], jnp.bfloat16),
        state=tuple(S(shp, jnp.dtype(dt))
                    for shp, dt in cc.state_shapes(cfg)))

    def sampling(n):
        f32 = jnp.float32
        return SamplingState(
            temperature=S((n,), f32), top_p=S((n,), f32), top_k=S((n,)),
            presence=S((n,), f32), frequency=S((n,), f32))

    state = E.DecodeState(
        last_token=S((B,)), positions=S((B,)),
        page_tables=S((B, max_pages)), active=S((B,)),
        mrope_delta=S((B,)), keys=S((B, 2), jnp.uint32),
        token_counts=S((B, cfg.vocab_size)), adapter_slots=S((B,)),
        sampling=sampling(B))
    bucket, rows = (0, 0) if program == "decode" else (512, 1)
    pargs = () if not bucket else (
        *(S((1, bucket)) for _ in range(5)), S((rows,)), S((rows,)),
        S((rows,)), S((rows, max_pages)), S((rows,)), sampling(rows),
        S((rows, 2), jnp.uint32), S((rows,)), S((rows,)))
    fn = E._build_ragged_step_fn(
        cfg, PAGE, "pallas", None, bucket, bool(bucket), rows, 1, 7)
    compiled = fn.lower(
        params, cache, state, pargs, S((B, 0)), S((B,)), S(()), None
    ).compile()
    text = compiled.as_text()
    assert "retention_decode_tpu" in text
    # the state pool is updated in place: aliased whole, and no temporary
    # of a tenth of its size
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cc.state_bytes(cfg)
    assert mem.temp_size_in_bytes < cc.state_bytes(cfg) // 2
    # ... through the fused tail of seven steps too, where a step that is not
    # the window's last reads the pool and writes nothing, and which step
    # that is is data: no copy of the pool (in any layout) around the kernel
    # or the loop, and nothing computed again
    import re

    assert not re.search(r"= f32\[2,24,8,8704,128\]\S* copy\(", text)
    assert ".remat" not in text


# benchmark/configs/laguna-xs2-int8.profile.yaml
LAGUNA_SLOTS, LAGUNA_WINDOW = 48, 512


@pytest.mark.parametrize("program", ["decode", "chunk_with_history"])
def test_deltanet_step_compiles_at_published_widths(one_chip, program):
    """A whole engine step of GigaChat3.5 cut to three layers (delta + dense,
    latent + held experts, delta + held experts; int8 weights, 64 slots) for
    the described chip: the delta decode kernel over the state pool in the
    carry, the chunked form in its chunk kernel, the latent kernel at 64 heads
    over a latent pool of ONE layer, the grouped product over 16 of 256
    experts, and both state arrays updated in place."""
    import dataclasses

    from helix_tpu.engine import engine as E
    from helix_tpu.engine.kv_cache import CacheConfig, PagedKVCache
    from helix_tpu.engine.sampling import SamplingState
    from helix_tpu.models.common import GIGACHAT35_432B
    from helix_tpu.models.llama import init_params

    cfg = dataclasses.replace(
        GIGACHAT35_432B, num_layers=3, first_k_dense=1, held_experts=(0, 16),
        layer_types=("deltanet", "attn", "deltanet"))
    B, max_pages, pages = 64, 160, 2048
    i32 = jnp.int32

    def S(shp, dt=i32):
        return jax.ShapeDtypeStruct(tuple(shp), dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), int8=True)))
    cc = CacheConfig(num_pages=pages, state_slots=B,
                     max_pages_per_seq=max_pages)
    ks, = cc.page_shapes(cfg)
    assert ks == (1, 16, 512 + 128)
    assert cc.state_shapes(cfg) == (
        ((2, B, 3, 16384), "bfloat16"), ((2, B, 64, 128, 128), "float32"))
    cache = PagedKVCache(
        k_pages=S((ks[0], pages) + ks[1:], jnp.bfloat16), v_pages=None,
        state=tuple(S(shp, jnp.dtype(dt))
                    for shp, dt in cc.state_shapes(cfg)))

    def sampling(n):
        f32 = jnp.float32
        return SamplingState(
            temperature=S((n,), f32), top_p=S((n,), f32), top_k=S((n,)),
            presence=S((n,), f32), frequency=S((n,), f32))

    state = E.DecodeState(
        last_token=S((B,)), positions=S((B,)),
        page_tables=S((B, max_pages)), active=S((B,)),
        mrope_delta=S((B,)), keys=S((B, 2), jnp.uint32),
        token_counts=S((B, cfg.vocab_size)), adapter_slots=S((B,)),
        sampling=sampling(B))
    bucket, rows = (0, 0) if program == "decode" else (512, 1)
    pargs = () if not bucket else (
        *(S((1, bucket)) for _ in range(5)), S((rows,)), S((rows,)),
        S((rows,)), S((rows, max_pages)), S((rows,)), sampling(rows),
        S((rows, 2), jnp.uint32), S((rows,)), S((rows,)))
    fn = E._build_ragged_step_fn(
        cfg, PAGE, "pallas", None, bucket, bool(bucket), rows, 1,
        0 if bucket else 7)
    compiled = fn.lower(
        params, cache, state, pargs, S((B, 0)), S((B,)), S(()), None
    ).compile()
    text = compiled.as_text()
    for kernel in ("deltanet_decode_tpu", "grouped_matmul_tpu",
                   "mla_ragged_paged_attention") + (
                       ("deltanet_chunk_tpu",) if bucket else ()):
        assert kernel in text, kernel
    # the state pool is updated in place: aliased whole, and no temporary
    # of half its size
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cc.state_bytes(cfg)
    assert mem.temp_size_in_bytes < cc.state_bytes(cfg)
    # ... with no copy of the matrix states in any layout around the kernels
    # or the fused tail's loop (the conv tail, 12.6 MB, is copied once or
    # twice a program), and nothing computed again
    import re

    assert not re.search(r"= f32\[2,64,64,128,128\]\S* copy\(", text)
    assert ".remat" not in text


@pytest.mark.parametrize("program", ["decode", "chunk_with_history",
                                     "chunk_of_64_with_history",
                                     "packed_wave"])
def test_window_step_compiles_at_published_widths(one_chip, program):
    """A whole engine step of Laguna-XS.2 cut to ONE period of four layers
    (full + dense, three sliding + held experts; int8 weights, 48 slots) for
    the described chip: the window kernel over the rings in the carry, the
    dense ragged kernel at a group of 6 over a page pool of ONE layer, the
    windowed flash attention of a cold wave, the grouped product over 32 of
    256 experts, and both rings updated in place."""
    import dataclasses

    import re

    from helix_tpu.engine import engine as E
    from helix_tpu.engine.kv_cache import CacheConfig, PagedKVCache
    from helix_tpu.engine.sampling import SamplingState
    from helix_tpu.models.common import LAGUNA_XS2
    from helix_tpu.models.llama import init_params

    cfg = dataclasses.replace(
        LAGUNA_XS2, num_layers=4, held_experts=(0, 32),
        layer_types=LAGUNA_XS2.layer_types[:4])
    B, max_pages, pages = LAGUNA_SLOTS, 160, 2048
    i32 = jnp.int32

    def S(shp, dt=i32):
        return jax.ShapeDtypeStruct(tuple(shp), dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), int8=True)))
    cc = CacheConfig(num_pages=pages, state_slots=B,
                     max_pages_per_seq=max_pages)
    ks, vs = cc.page_shapes(cfg)
    assert ks == vs == (1, 16, 8, 128)
    assert cc.state_shapes(cfg) == (
        ((3, B, LAGUNA_WINDOW, 8, 128), "bfloat16"),) * 2
    cache = PagedKVCache(
        k_pages=S((1, pages) + ks[1:], jnp.bfloat16),
        v_pages=S((1, pages) + vs[1:], jnp.bfloat16),
        state=tuple(S(shp, jnp.dtype(dt))
                    for shp, dt in cc.state_shapes(cfg)))

    def sampling(n):
        f32 = jnp.float32
        return SamplingState(
            temperature=S((n,), f32), top_p=S((n,), f32), top_k=S((n,)),
            presence=S((n,), f32), frequency=S((n,), f32))

    state = E.DecodeState(
        last_token=S((B,)), positions=S((B,)),
        page_tables=S((B, max_pages)), active=S((B,)),
        mrope_delta=S((B,)), keys=S((B, 2), jnp.uint32),
        token_counts=S((B, cfg.vocab_size)), adapter_slots=S((B,)),
        sampling=sampling(B))
    # (the window kernel's long block follows the bucket: 64 tokens a
    # block in both chunk programs, one block a row in the smaller)
    bucket, rows, hist = {"decode": (0, 0, False),
                          "chunk_with_history": (512, 1, True),
                          "chunk_of_64_with_history": (64, 1, True),
                          "packed_wave": (512, 32, False)}[program]
    pargs = () if not bucket else (
        *(S((1, bucket)) for _ in range(5)), S((rows,)), S((rows,)),
        S((rows,)), S((rows, max_pages)), S((rows,)), sampling(rows),
        S((rows, 2), jnp.uint32), S((rows,)), S((rows,)))
    fn = E._build_ragged_step_fn(
        cfg, PAGE, "pallas", None, bucket, hist, rows, 1,
        0 if bucket else 7)
    compiled = fn.lower(
        params, cache, state, pargs, S((B, 0)), S((B,)), S(()), None
    ).compile()
    text = compiled.as_text()
    for kernel in ("window_attention_tpu", "grouped_matmul_tpu",
                   "ragged_paged_attention_tpu"):
        assert kernel in text, kernel
    # the rings are updated in place: aliased whole, and no temporary of the
    # size of ONE of the two
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cc.state_bytes(cfg)
    assert mem.temp_size_in_bytes < cc.state_bytes(cfg) // 2
    # ... no copy of a ring pool (in any layout) around the kernel or the
    # loop, and nothing computed again
    assert not re.search(r"= bf16\[3,48,512,8,128\]\S* copy\(", text)
    # ... nor of the page pool the paged kernel's long block walks
    assert not re.search(r"= bf16\[1,2048,16,8,128\]\S* copy\(", text)
    assert ".remat" not in text


@pytest.mark.parametrize("bucket", [0, 128, 512])
def test_ssd_step_compiles_at_published_widths(one_chip, bucket):
    """A whole engine step of Nemotron-3-Super cut to three published layers
    in two blocks (attention + held latent experts, a Mamba-2 layer alone;
    int8 weights, 64 slots) for the described chip, the program that carries
    a chunk with history beside the decode rows: the state-space decode
    kernel over the state pool in the carry, the chunked form in its two
    halves (``ssd_chunk_tpu`` behind ``ops/ssd.py::state_free``; no loop of
    ``ssd_chunk`` is left), the paged kernel at 32 query over 2 kv heads, the
    one-operand grouped product over 128 of 512 experts in the latent, and
    both state arrays updated in place.  Bucket 0 is the decode-only program
    with its fused tail of seven steps, where a step that is not the
    window's last reads the state and writes nothing and which step that is
    is data: the window's tokens ride the tail's loop beside the pools.  A
    chunk of 128 tokens is ONE block of the chunked
    form: the program that PR 45's first chip run found transposing the
    whole state pool twice a layer; 512 tokens are four blocks in two passes
    of two (``ops/ssd.py::SLAB``), the program the cell's prompts run.  (What
    ``SLAB`` guards against, a layer's in-projection computed again, shows
    only in the whole 22-layer program: nothing here can assert it.)"""
    import dataclasses

    from helix_tpu.engine import engine as E
    from helix_tpu.engine.kv_cache import CacheConfig, PagedKVCache
    from helix_tpu.engine.sampling import SamplingState
    from helix_tpu.models.common import NEMOTRON3_SUPER_120B
    from helix_tpu.models.llama import init_params

    cfg = dataclasses.replace(
        NEMOTRON3_SUPER_120B, num_layers=3, hybrid_pattern="*EM",
        held_experts=(0, 128))
    assert cfg.ffns == ("moe", "none")
    B, max_pages, pages = 64, 160, 2048
    i32 = jnp.int32

    def S(shp, dt=i32):
        return jax.ShapeDtypeStruct(tuple(shp), dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), int8=True)))
    cc = CacheConfig(num_pages=pages, state_slots=B,
                     max_pages_per_seq=max_pages)
    ks, vs = cc.page_shapes(cfg)
    assert ks == vs == (1, 16, 2, 128)
    assert cc.state_shapes(cfg) == (
        ((1, B, 3, 10240), "bfloat16"), ((1, B, 64, 128, 128), "float32"))
    cache = PagedKVCache(
        k_pages=S((ks[0], pages) + ks[1:], jnp.bfloat16),
        v_pages=S((vs[0], pages) + vs[1:], jnp.bfloat16),
        state=tuple(S(shp, jnp.dtype(dt))
                    for shp, dt in cc.state_shapes(cfg)))

    def sampling(n):
        f32 = jnp.float32
        return SamplingState(
            temperature=S((n,), f32), top_p=S((n,), f32), top_k=S((n,)),
            presence=S((n,), f32), frequency=S((n,), f32))

    state = E.DecodeState(
        last_token=S((B,)), positions=S((B,)),
        page_tables=S((B, max_pages)), active=S((B,)),
        mrope_delta=S((B,)), keys=S((B, 2), jnp.uint32),
        token_counts=S((B, cfg.vocab_size)), adapter_slots=S((B,)),
        sampling=sampling(B))
    rows = 1 if bucket else 0
    pargs = () if not bucket else (
        *(S((1, bucket)) for _ in range(5)), S((rows,)), S((rows,)),
        S((rows,)), S((rows, max_pages)), S((rows,)), sampling(rows),
        S((rows, 2), jnp.uint32), S((rows,)), S((rows,)))
    fn = E._build_ragged_step_fn(
        cfg, PAGE, "pallas", None, bucket, bool(bucket), rows, 1,
        0 if bucket else 7)
    compiled = fn.lower(
        params, cache, state, pargs, S((B, 0)), S((B,)), S(()), None
    ).compile()
    text = compiled.as_text()
    for kernel in ("ssd_decode_tpu", "grouped_matmul_tpu",
                   "ragged_paged_attention") + (
                       ("ssd_chunk_tpu",) if bucket else ()):
        assert kernel in text, kernel
    assert "ragged-dot" not in text and "ragged_dot" not in text
    # the state pool is updated in place: aliased whole, no temporary of its
    # size and no copy of it in another layout
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cc.state_bytes(cfg)
    assert mem.temp_size_in_bytes < cc.state_bytes(cfg)
    import re

    # (of ``h``; the conv tail, 3.9 MB, is copied twice a program, as it was
    # before a window was fused)
    assert not re.search(r"= f32\[1,64,64,128,128\]\S* copy\(", text)
    assert ".remat" not in text
    if not bucket:
        # the decode kernel once a loop body (the stage's layer, the tail's),
        # and the window's tokens in the tail's loop: eight steps' ``dt x``
        # a slot and layer, in the pool's packed rows
        assert sum("tpu_custom_call" in ln and "ssd_decode_tpu" in ln
                   for ln in text.splitlines()) == 2
        assert re.search(
            r"%while\S* = \([^\n]*f32\[1,64,8,64,128\]", text)


# benchmark/configs/mellum2-12b-a2.5b-int8.profile.yaml
MELLUM_SLOTS, MELLUM_WINDOW, MELLUM_TABLE = 12, 1024, 544


@pytest.mark.parametrize("program", ["decode", "chunk_with_history",
                                     "chunk_of_64_with_history"])
def test_window_softmax_step_compiles_at_published_widths(one_chip, program):
    """A whole engine step of Mellum2-12B-A2.5B cut to ONE period of four
    layers (three sliding + experts, one full + experts; int8 weights, 12
    slots, every size of the cell's profile: a page table 544 wide over 6,529
    pages, 98,304 rows) for the described chip: the window kernel over rings
    of ``[1024, 4, 128]`` in the carry, the dense ragged kernel at a query
    group of 8 over a page pool of ONE layer, the grouped product over all 64
    experts, both rings updated in place and laid out at their counted bytes
    (``T(4,128)(2,1)``: no padded tile)."""
    import dataclasses
    import re

    from helix_tpu.engine import engine as E
    from helix_tpu.engine.kv_cache import CacheConfig, PagedKVCache
    from helix_tpu.engine.sampling import SamplingState
    from helix_tpu.models.common import MELLUM2_12B
    from helix_tpu.models.llama import init_params

    cfg = dataclasses.replace(
        MELLUM2_12B, num_layers=4, layer_types=MELLUM2_12B.layer_types[:4])
    assert cfg.held_experts is None and cfg.num_held_experts == 64
    B, max_pages = MELLUM_SLOTS, MELLUM_TABLE
    pages = B * max_pages + 1
    i32 = jnp.int32

    def S(shp, dt=i32):
        return jax.ShapeDtypeStruct(tuple(shp), dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), int8=True)))
    cc = CacheConfig(num_pages=pages, state_slots=B,
                     max_pages_per_seq=max_pages)
    ks, vs = cc.page_shapes(cfg)
    assert ks == vs == (1, 16, 4, 128)
    assert cc.state_shapes(cfg) == (
        ((3, B, MELLUM_WINDOW, 4, 128), "bfloat16"),) * 2
    cache = PagedKVCache(
        k_pages=S((1, pages) + ks[1:], jnp.bfloat16),
        v_pages=S((1, pages) + vs[1:], jnp.bfloat16),
        state=tuple(S(shp, jnp.dtype(dt))
                    for shp, dt in cc.state_shapes(cfg)))

    def sampling(n):
        f32 = jnp.float32
        return SamplingState(
            temperature=S((n,), f32), top_p=S((n,), f32), top_k=S((n,)),
            presence=S((n,), f32), frequency=S((n,), f32))

    state = E.DecodeState(
        last_token=S((B,)), positions=S((B,)),
        page_tables=S((B, max_pages)), active=S((B,)),
        mrope_delta=S((B,)), keys=S((B, 2), jnp.uint32),
        token_counts=S((B, cfg.vocab_size)), adapter_slots=S((B,)),
        sampling=sampling(B))
    bucket, rows, hist = {"decode": (0, 0, False),
                          "chunk_with_history": (512, 1, True),
                          "chunk_of_64_with_history": (64, 1, True)}[program]
    pargs = () if not bucket else (
        *(S((1, bucket)) for _ in range(5)), S((rows,)), S((rows,)),
        S((rows,)), S((rows, max_pages)), S((rows,)), sampling(rows),
        S((rows, 2), jnp.uint32), S((rows,)), S((rows,)))
    fn = E._build_ragged_step_fn(
        cfg, PAGE, "pallas", None, bucket, hist, rows, 1,
        0 if bucket else 7)
    compiled = fn.lower(
        params, cache, state, pargs, S((B, 0)), S((B,)), S(()), None
    ).compile()
    text = compiled.as_text()
    for kernel in ("window_attention_tpu", "grouped_matmul_tpu",
                   "ragged_paged_attention_tpu"):
        assert kernel in text, kernel
    assert "ragged-dot" not in text and "ragged_dot" not in text
    # a ring of 4 kv heads takes its counted bytes, in HBM as in the carry
    tiles = set(re.findall(
        r"bf16\[3,12,1024,4,128\]\{[^}]*T\(([0-9,]*)\)", text))
    assert tiles == {"4,128"}, tiles
    # the rings are updated in place: aliased whole, and no temporary of the
    # size of ONE of the two
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cc.state_bytes(cfg)
    assert mem.temp_size_in_bytes < cc.state_bytes(cfg) // 2
    # ... no copy of a ring pool (in any layout) around the kernel or the
    # loop, and nothing computed again
    assert not re.search(r"= bf16\[3,12,1024,4,128\]\S* copy\(", text)
    # ... nor of the page pool the paged kernel's long block walks
    assert not re.search(r"= bf16\[1,6529,16,4,128\]\S* copy\(", text)
    assert ".remat" not in text
    weights = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in jax.tree.leaves(params))
    held = weights + cc.state_bytes(cfg) + pages * cc.page_bytes(cfg)
    # the arguments are the weights, the rings, the pages and the decode
    # state (its 12 x 98,304 token counts the most of it): nothing is padded
    assert held <= mem.argument_size_in_bytes < held + (8 << 20)


# (query heads, kv heads, head width, pages a table) of the configurations
# whose cells send the paged kernel a chunk row and have no whole step above:
# the groups 7 -> 8, 4 -> 8, 4 packed to 8 and 16
CHUNK_ROW_GEOMETRY = {
    "qwen2-7b": (28, 4, 128, 64),
    "mistral-7b": (32, 8, 128, 64),
    "lfm2-8b-a1b": (32, 8, 64, 160),
    "nemotron-3-super": (32, 2, 128, 160),
    # a head of two lane tiles at a group of 8, a table 1,056 pages wide
    "qwen3-next": (16, 2, 256, 1056),
}


@pytest.mark.parametrize("rows", [1, 32], ids=["one_row", "a_wave_of_32"])
@pytest.mark.parametrize("geometry", sorted(CHUNK_ROW_GEOMETRY))
def test_paged_kernel_compiles_a_chunk_row_at_every_cells_geometry(
        one_chip, geometry, rows):
    """The paged kernel ALONE over a 512-token bucket with history, as the
    dispatcher hands it each configuration's heads (the long block: 128
    tokens at a group of 8 or under, 64 at 16; 16 where 32 rows share the
    bucket), for the described chip: Mosaic takes the form, the pools are
    read where they lie."""
    from helix_tpu.ops.paged import ragged_paged_attention
    from helix_tpu.ops.paged_kernel import paged_query_block

    H, KVH, D, max_pages = CHUNK_ROW_GEOMETRY[geometry]
    pack = max(1, 128 // D)
    T, L, pages = 512, 2, 2048

    def S(shp, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    assert paged_query_block(T, H * pack // KVH, rows, T) == (
        16 if rows > 1 else 64 if H * pack // KVH > 8 else 128)
    pool = S((L, pages, PAGE, KVH // pack, D * pack), jnp.bfloat16)
    compiled = jax.jit(lambda *a: ragged_paged_attention(
        *a, backend="pallas", max_q_len=T)).lower(
        S((T, H, D), jnp.bfloat16), S((T, KVH, D), jnp.bfloat16),
        S((T, KVH, D), jnp.bfloat16), pool, pool, S(()), S((rows,)),
        S((rows,)), S((rows,)), S((rows, max_pages))).compile()
    assert "ragged_paged_attention_tpu" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 24


@pytest.mark.parametrize("program", ["decode", "chunk_with_history"])
def test_sparse_latent_step_compiles_at_published_widths(one_chip, program):
    """A whole engine step of GLM-5 (the dense layer and two expert layers of
    its cut of eight, int8 weights, 16 slots, a page table 1,056 wide over
    16,897 pages) for the described chip: the scoring kernel over a row's
    gathered index keys, the choice (a decode row's ``lax.top_k``; a chunk's
    threshold kernel), the sparse attention kernel over the chosen rows
    (decode) or over a dense copy of the history under the mask it makes from
    the scores (a 512-token chunk), the row scatter into BOTH pools.  (A cold
    chunk is the latent kernel's with the table 1,056 wide in SMEM: compiled
    here at PR 53 and run in the cell; not kept, for the suite's time.)"""
    import dataclasses

    from helix_tpu.engine import engine as E
    from helix_tpu.engine.kv_cache import CacheConfig, PagedKVCache
    from helix_tpu.engine.sampling import SamplingState
    from helix_tpu.models.common import CATALOG
    from helix_tpu.models.llama import init_params

    cfg = dataclasses.replace(
        CATALOG["zai-org/GLM-5"], num_layers=3, first_k_dense=1,
        held_experts=(0, 16))
    B, max_pages, pages = 16, 1056, 16897
    i32 = jnp.int32

    def S(shp, dt=i32):
        return jax.ShapeDtypeStruct(tuple(shp), dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), int8=True)))
    ks, vs = CacheConfig(num_pages=pages).page_shapes(cfg)
    assert ks == (3, 16, 512 + 128) and vs == (3, 16, 128)
    cache = PagedKVCache(
        k_pages=S((ks[0], pages) + ks[1:], jnp.bfloat16),
        v_pages=S((vs[0], pages) + vs[1:], jnp.bfloat16))

    def sampling(n):
        f32 = jnp.float32
        return SamplingState(
            temperature=S((n,), f32), top_p=S((n,), f32), top_k=S((n,)),
            presence=S((n,), f32), frequency=S((n,), f32))

    state = E.DecodeState(
        last_token=S((B,)), positions=S((B,)),
        page_tables=S((B, max_pages)), active=S((B,)),
        mrope_delta=S((B,)), keys=S((B, 2), jnp.uint32),
        token_counts=S((B, cfg.vocab_size)), adapter_slots=S((B,)),
        sampling=sampling(B))
    bucket, rows = (0, 0) if program == "decode" else (512, 1)
    pargs = () if not bucket else (
        *(S((1, bucket)) for _ in range(5)), S((rows,)), S((rows,)),
        S((rows,)), S((rows, max_pages)), S((rows,)), sampling(rows),
        S((rows, 2), jnp.uint32))
    fn = E._build_ragged_step_fn(
        cfg, PAGE, "pallas", None, bucket, program == "chunk_with_history",
        rows, 1, 7)
    compiled = fn.lower(
        params, cache, state, pargs, S((B, 0)), S((B,)), S(()), None
    ).compile()
    text = compiled.as_text()
    # the kernels, by the names a trace finds them by
    # (benchmark/metrics/kernel.dsa_index_share.json, .mla_sparse_share)
    assert "dsa_index_scores_tpu" in text
    assert "mla_sparse_attention_tpu" in text
    assert "grouped_matmul_tpu" in text
    # a chunk's choice is the threshold kernel's (two numbers a query); a
    # decode row's is ``lax.top_k``
    assert ("dsa_threshold_tpu" in text) == (program != "decode")
    # both pools are updated in place: no pool-sized temporary
    pool_bytes = ks[0] * pages * 16 * (512 + 128) * 2
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < pool_bytes, mem.temp_size_in_bytes
    if program == "decode":
        # the two pools as the described chip lays them out, against the
        # benchmark's count (benchmark/lib/model_bytes_mla_dsa_moe.py): no
        # padding in either (640 and 128 lanes are whole tiles)
        import json
        import os

        from benchmark.lib import model_bytes_mla_dsa_moe as mb

        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "benchmark", "configs",
                "glm-5-int8.json")) as f:
            hf = dict(json.load(f), num_hidden_layers=3)
        pools = jax.jit(
            lambda: (jnp.zeros(cache.k_pages.shape, jnp.bfloat16),
                     jnp.zeros(cache.v_pages.shape, jnp.bfloat16)),
            out_shardings=(one_chip, one_chip)).lower().compile()
        lat, key = mb.token_bytes(hf)
        counted = pages * 16 * (lat + key)
        assert counted == pages * mb.page_bytes(hf, 16)
        # (the compiler adds the result tuple's own 512 bytes)
        assert 0 <= pools.memory_analysis().output_size_in_bytes - (
            counted) <= 4096
        assert key * pages * 16 == 3 * pages * 16 * 128 * 2


@pytest.mark.parametrize("program", ["decode", "t512_r1", "t512_r1_h"])
def test_delta_gqa_step_compiles_at_published_widths(one_chip, program):
    """The WHOLE cut of Qwen3-Next-80B-A3B (twelve layers: three periods of
    three delta layers and a gated attention layer, run as ONE group of two
    loop bodies; int8 weights, 16 slots, a page table 1,056 wide over 16,897
    pages, 256 of 512 experts held) for the described chip: the delta decode
    kernel over the state pool in the carry, the chunked form in its chunk
    kernel at 16 / 32 heads, the paged kernel at 16 / 2 heads of 256 lanes
    (decode rows; a 512-token chunk row's long blocks over the history: the
    form Mosaic refused until the rows past a step's end were zeroed where
    they land), the grouped product at 256 groups, both pools updated in
    place, and weights, pools and temporaries inside the chip's memory."""
    import dataclasses

    from helix_tpu.engine import engine as E
    from helix_tpu.engine.kv_cache import CacheConfig, PagedKVCache
    from helix_tpu.engine.sampling import SamplingState
    from helix_tpu.models.common import QWEN3_NEXT_80B
    from helix_tpu.models.llama import init_params

    cfg = dataclasses.replace(
        QWEN3_NEXT_80B, num_layers=12,
        layer_types=QWEN3_NEXT_80B.layer_types[:12], held_experts=(0, 256))
    assert [g.reps for g in cfg.layer_runs()] == [3] and cfg.loop_bodies == 2
    B, max_pages, pages = 16, 1056, 16897
    i32 = jnp.int32

    def S(shp, dt=i32):
        return jax.ShapeDtypeStruct(tuple(shp), dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0), int8=True)))
    cc = CacheConfig(num_pages=pages, state_slots=B,
                     max_pages_per_seq=max_pages)
    ks, vs = cc.page_shapes(cfg)
    assert ks == vs == (3, 16, 2, 256)
    assert cc.state_shapes(cfg) == (
        ((9, B, 3, 8192), "bfloat16"), ((9, B, 32, 128, 128), "float32"))
    cache = PagedKVCache(
        k_pages=S((ks[0], pages) + ks[1:], jnp.bfloat16),
        v_pages=S((vs[0], pages) + vs[1:], jnp.bfloat16),
        state=tuple(S(shp, jnp.dtype(dt))
                    for shp, dt in cc.state_shapes(cfg)))

    def sampling(n):
        f32 = jnp.float32
        return SamplingState(
            temperature=S((n,), f32), top_p=S((n,), f32), top_k=S((n,)),
            presence=S((n,), f32), frequency=S((n,), f32))

    state = E.DecodeState(
        last_token=S((B,)), positions=S((B,)),
        page_tables=S((B, max_pages)), active=S((B,)),
        mrope_delta=S((B,)), keys=S((B, 2), jnp.uint32),
        token_counts=S((B, cfg.vocab_size)), adapter_slots=S((B,)),
        sampling=sampling(B))
    bucket, rows = (0, 0) if program == "decode" else (512, 1)
    pargs = () if not bucket else (
        *(S((1, bucket)) for _ in range(5)), S((rows,)), S((rows,)),
        S((rows,)), S((rows, max_pages)), S((rows,)), sampling(rows),
        S((rows, 2), jnp.uint32), S((rows,)), S((rows,)))
    fn = E._build_ragged_step_fn(
        cfg, PAGE, "pallas", None, bucket, program.endswith("_h"), rows, 1,
        0 if bucket else 7)
    compiled = fn.lower(
        params, cache, state, pargs, S((B, 0)), S((B,)), S(()), None
    ).compile()
    text = compiled.as_text()
    for kernel in ("deltanet_decode_tpu", "grouped_matmul_tpu",
                   "ragged_paged_attention_tpu") + (
                       ("deltanet_chunk_tpu",) if bucket else ()):
        assert kernel in text, kernel
    assert "ragged-dot" not in text and "ragged_dot" not in text
    assert ".remat" not in text
    # both pools are updated in place: aliased whole, and no temporary of
    # the state pool's size; the whole step fits the chip
    mem = compiled.memory_analysis()
    pools = cc.total_bytes(cfg)
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < cc.state_bytes(cfg)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.5e9
    if program == "decode":
        # weights and pools as the described chip lays them out, against the
        # benchmark's count (benchmark/lib/model_bytes_deltanet_gqa_moe.py)
        import json
        import os

        from benchmark.lib import model_bytes_deltanet_gqa_moe as mb

        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "benchmark", "configs",
                "qwen3-next-80b-a3b-int8.json")) as f:
            hf = json.load(f)
        counted = (mb.weight_bytes(hf) + pages * mb.page_bytes(hf, 16)
                   + B * mb.state_bytes_per_slot(hf))
        # (beside them the decode state's arrays, 10 MB of token counts)
        assert 0 <= mem.argument_size_in_bytes - counted < 16e6
