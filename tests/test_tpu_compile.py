"""Compile the main path's kernels for a DESCRIBED TPU v5e, without the chip.

With its two sibling files, the only place tests describe the chip.  The TPU's compiler is installed in
the sandbox and compiles for a topology that is described, not attached
(``on-chip-measurement`` guide, section 2): what Mosaic refuses here it
refuses on the chip, which interpret-mode parity tests cannot see (tiling,
slices, VMEM, partitioning).  Nothing runs, so these say nothing about
results or times; ``chip_smoke.py`` does that on the chip.

Rules these files keep (or the whole suite counts 0 under xdist): the
topology is described inside a fixture, never while a module is imported,
never in a ``skipif`` or a ``parametrize`` argument; the fixtures
(``tests/tpu_topology.py``) are not ``autouse``; every compile happens in
the test's own process.  This file holds the KERNELS at published widths;
whole engine steps are in ``test_tpu_compile_steps.py``, apart so that no one
xdist worker (``--dist loadfile``: a file is one worker's) holds them all.  A
worker's process keeps the TPU library once it has described the chip, so
the two files pass side by side only where several processes may load it (the driver's test
command sets ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``; without it, run them in one
process, or a file at a time).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_topology import one_chip, topo  # noqa: F401

GEOMETRY = {  # (query heads, kv heads, head width, layers)
    "qwen2-7b": (28, 4, 128, 28),
    "llama3-8b": (32, 8, 128, 32),
}
# head width 64, two kv heads to a 128-lane tile of the pool; its six layers
# of pages (not in GEOMETRY: that is parametrised over an int8 pool too,
# which packed heads do not have)
LFM2_8B = (32, 8, 64, 6)
PAGE, PAGES, MAX_PAGES = 16, 2048, 64
DECODE_BATCH, PREFILL_LEN = 32, 512   # profiles/v5e1-qwen2-7b.yaml


def _ragged_args(geometry, kv, shape, sharding, heads=P(), pool=P(),
                 scales=P()):
    """ShapeDtypeStructs of one ragged-op call.  ``sharding(spec)`` places
    an argument; the specs only matter under a mesh."""
    H, KVH, D, L = GEOMETRY[geometry]
    T, R = (DECODE_BATCH, DECODE_BATCH) if shape.startswith("decode") else (
        PREFILL_LEN, 1)
    pack = 128 // D if D < 128 and 128 % D == 0 else 1
    PKVH, PD = KVH // pack, D * pack    # the pool's minor pair

    def S(shp, dt, spec=P()):
        return jax.ShapeDtypeStruct(shp, dt, sharding=sharding(spec))

    pool_dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    args = [
        S((T, H, D), jnp.bfloat16, heads),
        S((T, KVH, D), jnp.bfloat16, heads),
        S((T, KVH, D), jnp.bfloat16, heads),
        S((L, PAGES, PAGE, PKVH, PD), pool_dt, pool),
        S((L, PAGES, PAGE, PKVH, PD), pool_dt, pool),
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


# the static bound on a row's fresh tokens, as the engine passes it: a
# decode call's rows are one-token query blocks (``decode_one_token``),
# a chunk's are 8-token blocks, and so are a caller's that gives no bound
MAX_Q_LEN = {"decode": None, "decode_one_token": 1,
             "prefill_with_history": PREFILL_LEN}


@pytest.mark.parametrize("shape", sorted(MAX_Q_LEN))
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
def test_ragged_kernel_compiles(one_chip, geometry, kv, shape):
    compiled = _compile_ragged(
        _ragged_args(geometry, kv, shape, lambda spec: one_chip),
        max_q_len=MAX_Q_LEN[shape],
    )
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("shape", sorted(MAX_Q_LEN))
def test_ragged_kernel_compiles_at_head_width_64(one_chip, shape):
    """LFM2-8B-A1B's attention (32 / 8 heads of 64) in both block shapes:
    the dispatcher hands the kernel two kv heads a lane tile.  Asked for the
    pool as ``[P, 8, 64]`` Mosaic refuses the chunk's reshape ("unsupported
    shape cast"); ``check_geometry`` admits the width because the kv heads
    pack evenly."""
    from helix_tpu.ops.paged_kernel import check_geometry

    check_geometry(32, 8, 64)
    GEOMETRY["lfm2-8b-a1b"] = LFM2_8B
    try:
        args = _ragged_args("lfm2-8b-a1b", "bf16", shape,
                            lambda spec: one_chip)
    finally:
        del GEOMETRY["lfm2-8b-a1b"]
    assert args[3].shape == (6, PAGES, PAGE, 4, 128)
    compiled = _compile_ragged(args, max_q_len=MAX_Q_LEN[shape])
    assert "ragged_paged_attention_tpu" in compiled.as_text()


def test_flash_attention_compiles_at_head_width_64(one_chip):
    from helix_tpu.ops.attention import attention

    H, KVH, D, _ = LFM2_8B

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


def test_decode_kernel_is_in_the_compiled_text(one_chip):
    """One Pallas call, under the name the benchmark's trace readers count
    model steps by (``benchmark/metrics/step.decode_ms.json``)."""
    import re

    text = _compile_ragged(
        _ragged_args("qwen2-7b", "bf16", "decode_one_token",
                     lambda spec: one_chip),
        max_q_len=1,
    ).as_text()
    calls = re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == 1 and calls[0].startswith(
        "ragged_paged_attention_tpu"), calls


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
        "llama3-8b", kv, "decode_one_token",
        lambda spec: NamedSharding(mesh, spec),
        heads=P(None, "tp", None),
        pool=P(None, None, None, "tp", None),
        scales=P(None, None, "tp"),
    )
    if kv == "int8":
        with pytest.raises(UnsupportedKernelGeometry, match="sublane pack"):
            _compile_ragged(args, mesh=mesh, max_q_len=1)
        return
    text = _compile_ragged(args, mesh=mesh, max_q_len=1).as_text()
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


# ---- DeepSeek-V2-Lite: the latent kernel and the expert step ---------------

MLA_SHAPES = {  # tokens, rows, a row's most fresh tokens
    "decode": (64, 64, 1),
    "chunk_with_history": (512, 1, 512),
    "packed_cold": (512, 64, 512),
    "verify": (256, 64, 4),
}


@pytest.mark.parametrize("heads", [16, 64], ids=["deepseek", "gigachat"])
@pytest.mark.parametrize("shape", sorted(MLA_SHAPES))
def test_latent_kernel_compiles_at_the_published_geometry(one_chip, shape,
                                                          heads):
    """The latent kernel over the ONE-array pool (a token's latent and its
    lane-padded rope key in one row of 640 lanes: one DMA a page) at both
    served head counts: DeepSeek-V2-Lite's 16 over its 17 layers' pool,
    GigaChat3.5's 64 over its two latent layers'."""
    from helix_tpu.ops.paged import mla_ragged_paged_attention

    T, R, mq = MLA_SHAPES[shape]
    lat, rope, max_pages = 512, 64, 160
    H, L, pages = {16: (16, 17, 10240), 64: (64, 2, 2048)}[heads]

    def S(shp, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    args = (S((T, H, lat + rope)), S((T, lat)), S((T, rope)),
            S((L, pages, PAGE, lat + 128)),
            S((), jnp.int32), S((R,), jnp.int32), S((R,), jnp.int32),
            S((R,), jnp.int32), S((R, max_pages), jnp.int32))
    compiled = jax.jit(
        lambda *a: mla_ragged_paged_attention(
            *a, backend="pallas", max_q_len=mq)
    ).lower(*args).compile()
    # the name a trace finds the kernel by (benchmark/metrics/*.mla*.json)
    assert "mla_ragged_paged_attention_tpu" in compiled.as_text()


def test_latent_geometry_mosaic_refuses_is_a_typed_error():
    from helix_tpu.ops.mla_kernel import check_mla_geometry
    from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry

    with pytest.raises(UnsupportedKernelGeometry, match="128 lanes"):
        check_mla_geometry(16, 576, 64)


@pytest.mark.parametrize("rows", [384, 3072], ids=["decode", "chunk"])
def test_grouped_product_compiles_at_the_published_widths(one_chip, rows):
    """64 experts of 2048 x 1408, int8, the layer picked from a stack of
    16: gate and up in one call, then down, at the row tile the row count
    gives (whole-K weight blocks: 2 x 2 x 2.88 MB of VMEM and the slabs'
    converts, over the default scoped limit, so the kernel sets its own)."""
    from helix_tpu.ops.grouped_matmul import (
        grouped_matmul_tpu, row_tile, visit_plan)

    n, X, E, F = 16, 64, 2048, 1408
    tm = row_tile(rows, X)

    def S(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    def op(x, wg, sg, wu, su, wd, sd, sizes, layer):
        plan = visit_plan(sizes, rows, tm)
        h = grouped_matmul_tpu(
            x, wg, plan, layer, scale=sg, w2=wu, scale2=su,
            act=jax.nn.silu, tm=tm, out_dtype=x.dtype)
        return grouped_matmul_tpu(h, wd, plan, layer, scale=sd, tm=tm)

    up = (S((n, X, E, F), jnp.int8), S((n, X, 1, F), jnp.float32))
    down = (S((n, X, F, E), jnp.int8), S((n, X, 1, E), jnp.float32))
    compiled = jax.jit(op).lower(
        S((rows, E), jnp.bfloat16), *up, *up, *down, S((X,), jnp.int32),
        S((), jnp.int32)).compile()
    assert compiled.as_text().count("grouped_matmul_tpu") >= 2
    # no slice of the stack is copied out for the kernel
    assert compiled.memory_analysis().temp_size_in_bytes < X * E * F


@pytest.mark.parametrize("terms", [1, 4, 8])
def test_retention_decode_kernel_compiles_at_the_published_geometry(
        one_chip, terms):
    """Brumby-14B's decode kernel: 24 rows, 8 kv heads of 5 query heads,
    width 128, a state of 8,704 x 128 float32 a head in a pool of ten layers
    (8.6 GB), donated: aliased to its output, no copy.  ``terms``: the
    tokens a fused window commits at once (1: a step that stands alone);
    whether a call commits or only reads is data."""
    from helix_tpu.ops.retention import held_rows
    from helix_tpu.ops.retention_kernel import retention_decode_tpu

    B, KVH, G, d, L = 24, 8, 5, 128, 10

    def S(shp, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    pool = S((L, B, KVH, held_rows(d), d))
    compiled = jax.jit(retention_decode_tpu, donate_argnums=(4,)).lower(
        S((B, KVH, G, d)), S((B, KVH, terms, d)), S((B, KVH, terms, d)),
        S((B, KVH, 1, d)), pool, S((), jnp.int32), S((B,), jnp.int32),
        S((), jnp.int32), S((), jnp.bool_)).compile()
    assert "retention_decode_tpu" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = L * B * KVH * held_rows(d) * d * 4
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 100


@pytest.mark.parametrize("tokens,rows", [(512, 1), (512, 24), (16, 24)],
                         ids=["one_row", "wave", "short_wave"])
def test_retention_chunked_form_compiles_at_the_published_geometry(
        one_chip, tokens, rows):
    """Brumby's chunked form over a prefill segment (``ops/retention.py::
    retention_rows``): the state-free half in XLA and the chunk kernel, 8
    kv heads of 5 query heads, a state of 8,704 x 128 a head in a pool of
    ten layers and 24 slots, donated: aliased to its output, and no
    ``phi(Q)`` (89 MB a kv head) among the temporaries."""
    import functools

    from helix_tpu.ops.retention import held_rows, retention_rows

    H, KVH, d, L, N = 40, 8, 128, 10, 24

    def S(shp, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    vec = S((rows,), jnp.int32)
    compiled = jax.jit(
        functools.partial(retention_rows, backend="pallas"),
        donate_argnums=(8, 9)).lower(
        S((tokens, H, d)), S((tokens, KVH, d)), S((tokens, KVH, d)),
        S((tokens, KVH)), vec, vec, vec, vec,
        S((L, N, KVH, held_rows(d), d)), S((L, N, KVH, d, d)),
        S((), jnp.int32)).compile()
    assert "retention_chunk_tpu" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= L * N * KVH * (held_rows(d) + d) * d * 4
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


def test_deltanet_decode_kernel_compiles_at_the_published_geometry(one_chip):
    """GigaChat3.5's decode kernel: 64 rows, 64 value heads, a state of 128 x
    128 float32 a head in a pool of seven layers (1.88 GB), donated: aliased
    to its output, no copy."""
    from helix_tpu.ops.deltanet_kernel import deltanet_decode_tpu

    B, H, d, L = 64, 64, 128, 7

    def S(shp, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    compiled = jax.jit(deltanet_decode_tpu, donate_argnums=(5,)).lower(
        S((B, H, d)), S((B, H, d)), S((B, H, d)), S((B, H)), S((B, H)),
        S((L, B, H, d, d)), S((), jnp.int32), S((B,), jnp.int32),
        S((), jnp.int32)).compile()
    assert "deltanet_decode_tpu" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = L * B * H * d * d * 4
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 100


@pytest.mark.parametrize("tokens,rows", [(512, 1), (512, 64), (16, 64)],
                         ids=["one_row", "wave", "short_wave"])
def test_deltanet_chunked_form_compiles_at_the_published_geometry(
        one_chip, tokens, rows):
    """GigaChat3.5's chunked delta rule over a prefill segment (``ops/
    deltanet.py::delta_rows``): the state-free half in XLA and the chunk
    kernel, 64 value heads of 128 x 128 in a pool of seven layers, donated:
    aliased to its output; a pass of eight chunks at a time, so a wave of 64
    rows holds no more temporaries than one row does."""
    import functools

    from helix_tpu.ops.deltanet import delta_rows

    H, d, L, N = 64, 128, 7, 64

    def S(shp, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    vec = S((rows,), jnp.int32)
    compiled = jax.jit(
        functools.partial(delta_rows, backend="pallas"),
        donate_argnums=(9,)).lower(
        S((tokens, H, d)), S((tokens, H, d)), S((tokens, H, d)),
        S((tokens, H)), S((tokens, H)), vec, vec, vec, vec,
        S((L, N, H, d, d)), S((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "deltanet_chunk_tpu" in text
    # no triangular solve is left: the inverse is products
    assert "TriangularSolve" not in text and "InvertDiagBlocks" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= L * N * H * d * d * 4
    assert mem.temp_size_in_bytes < 128 * 2 ** 20


@pytest.mark.parametrize("rows", [512, 4608], ids=["decode", "chunk"])
def test_grouped_product_compiles_at_7168_by_4096(one_chip, rows):
    """16 held experts of 7168 x 2048, int8, the layer picked from a stack of
    eight: gate and up in one call (a group's two whole ``[7168, 2048]``
    blocks: 29 MB a buffer, 59 MB double-buffered, under the kernel's own
    VMEM limit with the slabs' converts), the clamp inside it, then down; at
    the row tile the rows an expert gets on average give (64 x 8 and 576 x 8
    assignments of which a sixteenth are held)."""
    from helix_tpu.ops.grouped_matmul import (
        grouped_matmul_tpu, row_tile, visit_plan)

    n, X, E, F = 8, 16, 7168, 2048
    tm = row_tile(rows * X // 256, X)
    assert tm == (32 if rows == 512 else 128)

    def S(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    def op(x, wg, sg, wu, su, wd, sd, sizes, layer):
        plan = visit_plan(sizes, rows, tm)
        h = grouped_matmul_tpu(
            x, wg, plan, layer, scale=sg, w2=wu, scale2=su,
            act=jax.nn.silu, limit=10.0, tm=tm, out_dtype=x.dtype)
        return grouped_matmul_tpu(h, wd, plan, layer, scale=sd, tm=tm)

    up = (S((n, X, E, F), jnp.int8), S((n, X, 1, F), jnp.float32))
    down = (S((n, X, F, E), jnp.int8), S((n, X, 1, E), jnp.float32))
    compiled = jax.jit(op).lower(
        S((rows, E), jnp.bfloat16), *up, *up, *down, S((X,), jnp.int32),
        S((), jnp.int32)).compile()
    assert compiled.as_text().count("grouped_matmul_tpu") >= 2
    # no slice of the stack is copied out for the kernel
    assert compiled.memory_analysis().temp_size_in_bytes < E * F


# ---- Laguna-XS.2: window layers beside full ones (ISSUE 41) -------------------

LAGUNA_SLOTS, LAGUNA_WINDOW = 48, 512


@pytest.mark.parametrize("shape", ["decode", "chunk_with_history",
                                   "rows_on_one_axis"])
def test_window_kernel_compiles_at_the_published_geometry(one_chip, shape):
    """The window call at 64 query / 8 kv heads of 128 over rings of 512 in
    a pool of 30 layers and 48 slots: 48 one-token rows, a 512-token chunk
    row (four 128-token blocks that share the ring their first block fetched
    and laid out a kv head at a time), and eight rows on one axis."""
    from helix_tpu.ops.window import window_attention

    H, KVH, D, L = 64, 8, 128, 30
    T, R, mq = {"decode": (LAGUNA_SLOTS, LAGUNA_SLOTS, 1),
                "chunk_with_history": (PREFILL_LEN, 1, PREFILL_LEN),
                "rows_on_one_axis": (PREFILL_LEN + 16, 8, PREFILL_LEN)}[shape]

    def S(shp, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    ring = S((L, LAGUNA_SLOTS, LAGUNA_WINDOW, KVH, D), jnp.bfloat16)
    compiled = jax.jit(lambda *a: window_attention(
        *a, backend="pallas", max_q_len=mq)).lower(
        S((T, H, D), jnp.bfloat16), S((T, KVH, D), jnp.bfloat16),
        S((T, KVH, D), jnp.bfloat16), ring, ring, S(()), S((R,)), S((R,)),
        S((R,)), S((R,))).compile()
    assert "window_attention_tpu" in compiled.as_text()
    # the rings are read where they lie: no copy of a pool (1.5 GB each)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 24


@pytest.mark.parametrize("shape", sorted(MAX_Q_LEN))
def test_ragged_kernel_compiles_at_a_query_group_of_6(one_chip, shape):
    """Laguna's full layers: 48 query heads over 8 kv heads of 128, the
    group of 6 padded to a sublane tile of 8 in both block shapes."""
    GEOMETRY["laguna-full"] = (48, 8, 128, 10)
    try:
        compiled = _compile_ragged(
            _ragged_args("laguna-full", "bf16", shape, lambda spec: one_chip),
            max_q_len=MAX_Q_LEN[shape])
    finally:
        del GEOMETRY["laguna-full"]
    assert "ragged_paged_attention_tpu" in compiled.as_text()


@pytest.mark.parametrize("rows", [384, 4480], ids=["decode", "chunk"])
def test_grouped_product_compiles_at_2048_by_512(one_chip, rows):
    """32 held experts of 2048 x 512, int8, the layer picked from a stack of
    27: a decode step's 48 x 8 assignments (1.5 rows a held expert of the
    256 routed) and a chunk program's (512 + 48) x 8."""
    from helix_tpu.ops.grouped_matmul import (
        grouped_matmul_tpu, row_tile, visit_plan)

    n, X, E, F = 27, 32, 2048, 512
    tm = row_tile(rows * X // 256, X)

    def S(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    def op(x, wg, sg, wu, su, wd, sd, sizes, layer):
        plan = visit_plan(sizes, rows, tm)
        h = grouped_matmul_tpu(
            x, wg, plan, layer, scale=sg, w2=wu, scale2=su,
            act=jax.nn.silu, tm=tm, out_dtype=x.dtype)
        return grouped_matmul_tpu(h, wd, plan, layer, scale=sd, tm=tm)

    up = (S((n, X, E, F), jnp.int8), S((n, X, 1, F), jnp.float32))
    down = (S((n, X, F, E), jnp.int8), S((n, X, 1, E), jnp.float32))
    compiled = jax.jit(op).lower(
        S((rows, E), jnp.bfloat16), *up, *up, *down, S((X,), jnp.int32),
        S((), jnp.int32)).compile()
    assert compiled.as_text().count("grouped_matmul_tpu") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < X * E * F


# ---- Nemotron-3-Super: Mamba-2 layers, experts in a latent (ISSUE 45) -------


@pytest.mark.parametrize("terms", [1, 8], ids=["alone", "window_of_8"])
def test_ssd_decode_kernel_compiles_at_the_published_geometry(one_chip, terms):
    """Nemotron-3-Super's decode kernel: 64 rows, 128 heads of 64 over a state
    of 128 in groups of 16 heads, float32, two heads to a lane tile, in a pool
    of ten layers (2.68 GB), donated: aliased to its output, no copy.  With
    room for one token (the step that stands alone) and for a fused window's
    eight, how many it commits a traced scalar."""
    from helix_tpu.ops.ssd_kernel import ssd_decode_tpu

    B, H, P, G, N, L = 64, 128, 64, 8, 128, 10

    def S(shp, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    compiled = jax.jit(ssd_decode_tpu, donate_argnums=(4,)).lower(
        S((B, terms, H // 2, 128)), S((B, H)), S((B, G, terms, N)),
        S((B, G, N)),
        S((L, B, H // 2, N, 128)), S((), jnp.int32), S((B,), jnp.int32),
        S((), jnp.int32), S((), jnp.int32)).compile()
    assert "ssd_decode_tpu" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = L * B * H * P * N * 4
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 100


@pytest.mark.parametrize("backend", ["pallas", None],
                         ids=["two_halves", "loop"])
@pytest.mark.parametrize("tokens,rows", [(512, 1), (512, 64), (16, 64)],
                         ids=["one_row", "wave", "short_wave"])
def test_ssd_chunked_form_compiles_at_the_published_geometry(
        one_chip, tokens, rows, backend):
    """The chunked form over a prefill segment (``ops/ssd.py::ssd_rows``) at
    the published block of 128, the pool donated and aliased to its output.
    ``two_halves``: what a TPU runs, the state-free half in XLA and
    ``ssd_chunk_tpu``, two blocks a pass (``ops/ssd.py::SLAB``), so a wave of
    64 rows holds no more temporaries than one row does.  ``loop``: what this host's backend
    resolves to, the plain ``jax.numpy`` loop a block at a time, as it
    lowered before the kernel was written."""
    import functools

    from helix_tpu.ops.ssd import ssd_rows

    H, P, G, N, L, B = 128, 64, 8, 128, 10, 64

    def S(shp, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    vec = S((rows,), jnp.int32)
    compiled = jax.jit(
        functools.partial(ssd_rows, chunk=128, backend=backend),
        donate_argnums=(9,)).lower(
        S((tokens, H, P)), S((tokens, H)), S((tokens, H)),
        S((tokens, G, N)), S((tokens, G, N)), vec, vec, vec, vec,
        S((L, B, H // 2, N, 128)), S((), jnp.int32)).compile()
    assert ("ssd_chunk_tpu" in compiled.as_text()) == (backend == "pallas")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= L * B * H * P * N * 4
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("rows", [1408, 12672], ids=["decode", "chunk"])
def test_ungated_grouped_product_compiles_at_1024_by_2688(one_chip, rows):
    """128 held experts of 1024 x 2688 in the latent, int8, the layer picked
    from a stack of eight, ONE operand and relu2 in the first call (no gate
    matrix), then down; at the row tile that 64 x 22 and 576 x 22 assignments
    of which a quarter are held give."""
    from helix_tpu.ops.grouped_matmul import (
        grouped_matmul_tpu, relu2, row_tile, visit_plan)

    n, X, E, F = 8, 128, 1024, 2688
    tm = row_tile(rows * X // 512, X)
    assert tm == (32 if rows == 1408 else 128)

    def S(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    def op(x, wu, su, wd, sd, sizes, layer):
        plan = visit_plan(sizes, rows, tm)
        h = grouped_matmul_tpu(
            x, wu, plan, layer, scale=su, act=relu2, tm=tm,
            out_dtype=x.dtype)
        return grouped_matmul_tpu(h, wd, plan, layer, scale=sd, tm=tm)

    up = (S((n, X, E, F), jnp.int8), S((n, X, 1, F), jnp.float32))
    down = (S((n, X, F, E), jnp.int8), S((n, X, 1, E), jnp.float32))
    compiled = jax.jit(op).lower(
        S((rows, E), jnp.bfloat16), *up, *down, S((X,), jnp.int32),
        S((), jnp.int32)).compile()
    assert compiled.as_text().count("grouped_matmul_tpu") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < X * E * F


# ---- Mellum2-12B-A2.5B: rings of 1,024 over 4 kv heads (ISSUE 48) -----------

MELLUM_SLOTS, MELLUM_WINDOW, MELLUM_TABLE = 12, 1024, 544


@pytest.mark.parametrize("shape", ["decode", "chunk_with_history",
                                   "rows_on_one_axis"])
def test_window_kernel_compiles_at_a_ring_of_1024_over_4_kv_heads(
        one_chip, shape):
    """The window call at 32 query / 4 kv heads of 128 (a query group of 8)
    over rings of 1,024 in a pool of 21 layers and 12 slots: Laguna's bytes a
    ring in another tiling (twice the rows, half the kv heads).  The pool's
    ``(4, 128)`` bf16 minor pair is tiled ``T(4,128)(2,1)`` in HBM: the
    counted bytes, no padded tile."""
    import re

    from helix_tpu.ops.window import window_attention

    H, KVH, D, L = 32, 4, 128, 21
    T, R, mq = {"decode": (MELLUM_SLOTS, MELLUM_SLOTS, 1),
                "chunk_with_history": (PREFILL_LEN, 1, PREFILL_LEN),
                "rows_on_one_axis": (PREFILL_LEN + 16, 8, PREFILL_LEN)}[shape]

    def S(shp, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    ring = S((L, MELLUM_SLOTS, MELLUM_WINDOW, KVH, D), jnp.bfloat16)
    compiled = jax.jit(lambda *a: window_attention(
        *a, backend="pallas", max_q_len=mq)).lower(
        S((T, H, D), jnp.bfloat16), S((T, KVH, D), jnp.bfloat16),
        S((T, KVH, D), jnp.bfloat16), ring, ring, S(()), S((R,)), S((R,)),
        S((R,)), S((R,))).compile()
    text = compiled.as_text()
    assert "window_attention_tpu" in text
    layouts = set(re.findall(
        r"bf16\[21,12,1024,4,128\]\{[^}]*T\(([^}]*)\}", text))
    assert layouts == {"4,128)(2,1)"}, layouts
    mem = compiled.memory_analysis()
    # the rings are read where they lie, at their counted bytes: two pools of
    # 21 x 12 x 1,024 x 4 x 128 x 2 B among the arguments and no copy of one
    pool = L * MELLUM_SLOTS * MELLUM_WINDOW * KVH * D * 2
    assert 2 * pool <= mem.argument_size_in_bytes < 2 * pool + (1 << 23)
    assert mem.temp_size_in_bytes < 1 << 24


@pytest.mark.parametrize("shape", sorted(MAX_Q_LEN))
def test_ragged_kernel_compiles_at_a_page_table_544_wide(one_chip, shape):
    """Mellum's full layers: 32 query over 4 kv heads of 128, seven layers of
    pages ``[16, 4, 128]`` and a page table 544 wide in SMEM (8,704 tokens a
    row; 64 to 160 elsewhere), decode rows and a 512-token chunk row."""
    from helix_tpu.ops.paged import ragged_paged_attention

    H, KVH, D, L, pages = 32, 4, 128, 7, MELLUM_SLOTS * MELLUM_TABLE + 1
    T, R = (MELLUM_SLOTS, MELLUM_SLOTS) if shape.startswith("decode") else (
        PREFILL_LEN, 1)

    def S(shp, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    pool = S((L, pages, PAGE, KVH, D), jnp.bfloat16)
    compiled = jax.jit(lambda *a: ragged_paged_attention(
        *a, backend="pallas", max_q_len=MAX_Q_LEN[shape])).lower(
        S((T, H, D), jnp.bfloat16), S((T, KVH, D), jnp.bfloat16),
        S((T, KVH, D), jnp.bfloat16), pool, pool, S(()), S((R,)), S((R,)),
        S((R,)), S((R, MELLUM_TABLE))).compile()
    assert "ragged_paged_attention_tpu" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 24


@pytest.mark.parametrize("rows", [96, 4192], ids=["decode", "chunk"])
def test_grouped_product_compiles_at_2304_by_896(one_chip, rows):
    """All 64 experts of 2304 x 896, int8, the layer picked from a stack of
    21: a decode step's 12 x 8 assignments (1.5 rows an expert) and a chunk
    program's (512 + 12) x 8 (65 rows an expert)."""
    from helix_tpu.ops.grouped_matmul import (
        grouped_matmul_tpu, row_tile, visit_plan)

    n, X, E, F = 21, 64, 2304, 896
    tm = row_tile(rows, X)

    def S(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    def op(x, wg, sg, wu, su, wd, sd, sizes, layer):
        plan = visit_plan(sizes, rows, tm)
        h = grouped_matmul_tpu(
            x, wg, plan, layer, scale=sg, w2=wu, scale2=su,
            act=jax.nn.silu, tm=tm, out_dtype=x.dtype)
        return grouped_matmul_tpu(h, wd, plan, layer, scale=sd, tm=tm)

    up = (S((n, X, E, F), jnp.int8), S((n, X, 1, F), jnp.float32))
    down = (S((n, X, F, E), jnp.int8), S((n, X, 1, E), jnp.float32))
    compiled = jax.jit(op).lower(
        S((rows, E), jnp.bfloat16), *up, *up, *down, S((X,), jnp.int32),
        S((), jnp.int32)).compile()
    assert compiled.as_text().count("grouped_matmul_tpu") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < X * E * F


# ---- GLM-5: a chunk's choice behind the indexer -----------------------------

@pytest.mark.parametrize("rows", [1, 4], ids=["one_row", "four_rows"])
def test_chunk_choice_kernels_compile_at_the_published_geometry(one_chip,
                                                                rows):
    """``dsa_threshold_tpu`` and the chunk form of ``mla_sparse_attention_
    tpu`` at GLM-5's sizes: 512 flat queries of 64 heads over rows of 640
    lanes behind a page table 16,896 wide (33 key blocks of 512 and the
    fresh block: the threshold's scratch is 34 x 64 x 512 float32)."""
    from helix_tpu.ops.dsa_kernel import (
        dsa_threshold_tpu, mla_sparse_chunk_attention_tpu)

    T, S, H, W, lat = 512, 16896, 64, 640, 512

    def A(shp, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    scores = (A((rows, T, S), jnp.float32), A((T, T), jnp.float32))
    extents = (A((rows,)), A((rows,)), A((rows,)))
    compiled = jax.jit(lambda *a: dsa_threshold_tpu(*a, topk=2048)).lower(
        *scores, *extents).compile()
    assert "dsa_threshold_tpu" in compiled.as_text()
    compiled = jax.jit(
        lambda *a: mla_sparse_chunk_attention_tpu(*a, latent=lat)).lower(
        A((T, H, W), jnp.bfloat16), A((rows, S, W), jnp.bfloat16),
        A((T, W), jnp.bfloat16), *scores, A((rows, T), jnp.uint32),
        A((rows, T)), *extents).compile()
    # the name a trace finds the kernel by
    # (benchmark/metrics/kernel.mla_sparse_share.json)
    assert "mla_sparse_attention_tpu" in compiled.as_text()
    # no mask, bias or second copy of the scores beside the kernel
    assert compiled.memory_analysis().temp_size_in_bytes < T * S
