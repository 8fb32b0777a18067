"""The grouped expert product kernel (``helix_tpu/ops/grouped_matmul.py``) in
interpret mode at small sizes: against ``lax.ragged_dot`` + scale and a plain
loop over the groups, its visit plan against counts by hand, and the dropless
expert layer (``models/moe.py``) through it against the layer through
``ragged_dot``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helix_tpu.models.common import ModelConfig
from helix_tpu.models.llama import init_params
from helix_tpu.models.moe import grouped_backend, moe_ffn
from helix_tpu.ops.grouped_matmul import (
    check_grouped_geometry,
    grouped_matmul_tpu,
    row_tile,
    visit_plan,
)
from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry

X, K, N, LAYERS = 8, 256, 128, 3
SHAPES = {
    # rows, group sizes, row tile: a decode segment (few rows an expert,
    # several empty experts, masked tokens past the last group, rows that
    # do not fill their last tile) and a chunk (skewed, one group over
    # several row tiles, masked tokens past the last group)
    "decode": (48, [0, 5, 9, 0, 3, 0, 14, 6], 32),
    "chunk": (192, [3, 0, 150, 5, 0, 10, 7, 2], 64),
}


def _operands(weights, stacked, rows, seed=0):
    rng = np.random.default_rng(seed)
    n = LAYERS if stacked else 1
    x = jnp.asarray(rng.standard_normal((rows, K)), jnp.bfloat16)
    if weights == "int8":
        w = jnp.asarray(rng.integers(-127, 128, (n, X, K, N)), jnp.int8)
        scale = jnp.asarray(rng.random((n, X, 1, N)) * 0.01 + 0.001,
                            jnp.float32)
    else:
        w = jnp.asarray(rng.standard_normal((n, X, K, N)), jnp.bfloat16)
        scale = None
    return x, w, scale, (n - 1 if stacked else 0)


def _loop_over_groups(x, w, scale, sizes):
    """Plain float32: each group's rows against its own matrix."""
    out, start = [], 0
    for g, size in enumerate(sizes):
        m = np.asarray(w[g], np.float32)
        if scale is not None:
            m = m * np.asarray(scale[g])
        out.append(np.asarray(x, np.float32)[start:start + size] @ m)
        start += size
    return np.concatenate(out)


@pytest.mark.parametrize("stacked", [False, True], ids=["layer", "stack"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("weights", ["int8", "bf16"])
def test_kernel_equals_ragged_dot_and_a_loop_over_groups(
        weights, shape, stacked):
    rows, sizes, tm = SHAPES[shape]
    routed = sum(sizes)
    assert routed < rows                        # masked tokens are present
    x, w, scale, layer = _operands(weights, stacked, rows)
    plan = visit_plan(jnp.asarray(sizes, jnp.int32), rows, tm)
    got = np.asarray(grouped_matmul_tpu(
        x, w, plan, layer, scale=scale, tm=tm, interpret=True))[:routed]
    oracle = np.asarray(jax.lax.ragged_dot(
        x, w[layer], jnp.asarray(sizes, jnp.int32),
        preferred_element_type=jnp.float32))[:routed]
    if scale is not None:
        oracle = oracle * np.asarray(
            scale[layer])[np.repeat(np.arange(X), sizes), 0]
    loop = _loop_over_groups(
        x, w[layer], None if scale is None else scale[layer], sizes)
    top = np.abs(loop).max()
    assert np.abs(got - oracle).max() < 1e-5 * top
    assert np.abs(got - loop).max() < 1e-5 * top
    if shape == "chunk":
        # one group spans several row tiles, and tiles are shared
        assert 150 > 2 * tm and int(plan[3][0]) > -(-routed // tm)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_gate_and_up_in_one_call_write_act_gate_times_up(shape):
    rows, sizes, tm = SHAPES[shape]
    routed = sum(sizes)
    x, w, scale, layer = _operands("int8", True, rows)
    _, w2, scale2, _ = _operands("int8", True, rows, seed=1)
    plan = visit_plan(jnp.asarray(sizes, jnp.int32), rows, tm)
    kw = dict(tm=tm, interpret=True)
    gate = grouped_matmul_tpu(x, w, plan, layer, scale=scale, **kw)
    up = grouped_matmul_tpu(x, w2, plan, layer, scale=scale2, **kw)
    want = (jax.nn.silu(gate) * up).astype(jnp.bfloat16)[:routed]
    got = grouped_matmul_tpu(
        x, w, plan, layer, scale=scale, w2=w2, scale2=scale2,
        act=jax.nn.silu, out_dtype=jnp.bfloat16, **kw)[:routed]
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32))


@pytest.mark.parametrize("rows,groups,tm", [
    (384, 64, 64),      # 64 decode slots x 6 choices: 6 rows an expert
    (6, 64, 32),        # one live slot
    (96, 64, 32), (768, 64, 128),
    (3072, 64, 128),    # a 512-token chunk: 48 rows an expert
    (49152, 64, 128),   # never over the MXU's 128 rows
    (48, 8, 64), (192, 8, 128),
])
def test_row_tile_follows_the_rows_an_expert_gets(rows, groups, tm):
    assert row_tile(rows, groups) == tm


@pytest.mark.parametrize("seed", range(6))
def test_visit_plan_covers_every_routed_row_once(seed):
    rng = np.random.default_rng(seed)
    rows = int(rng.choice([48, 96, 192, 200]))
    tm = int(rng.choice([16, 32, 64]))
    routed = int(rng.integers(0, rows + 1))
    sizes = rng.multinomial(routed, rng.dirichlet(np.full(X, 0.5)))
    offsets, group, tile, count = (
        np.asarray(a) for a in visit_plan(jnp.asarray(sizes), rows, tm))
    count = int(count[0])
    assert len(group) == -(-rows // tm) + X - 1 and count <= len(group)
    np.testing.assert_array_equal(offsets, np.concatenate([[0], np.cumsum(sizes)]))
    seen = np.zeros(rows, np.int32)
    for g, t in zip(group[:count], tile[:count]):
        lo, hi = max(offsets[g], t * tm), min(offsets[g + 1], (t + 1) * tm)
        assert hi > lo                      # no visit without a row
        seen[lo:hi] += 1
    np.testing.assert_array_equal(seen[:routed], 1)
    np.testing.assert_array_equal(seen[routed:], 0)
    # a group's visits are consecutive (its weight is fetched once), and
    # the skipped visits repeat the last one's blocks
    live = group[:count]
    assert np.all(np.diff(live) >= 0) and np.all(np.diff(tile[:count]) >= 0)
    if count:
        assert np.all(group[count:] == group[count - 1])
        assert np.all(tile[count:] == tile[count - 1])


def test_geometry_the_kernel_refuses_is_refused_by_name_and_falls_back():
    check_grouped_geometry(2048, 1408)          # DeepSeek-V2-Lite gate / up
    check_grouped_geometry(1408, 2048)          # down
    with pytest.raises(UnsupportedKernelGeometry, match="128 lanes"):
        check_grouped_geometry(2048, 1400)
    x = jnp.zeros((16, 64), jnp.bfloat16)
    w = jnp.zeros((1, X, 64, 96), jnp.bfloat16)
    plan = visit_plan(jnp.full((X,), 2, jnp.int32), 16, 16)
    with pytest.raises(UnsupportedKernelGeometry, match=r"\[64, 96\]"):
        grouped_matmul_tpu(x, w, plan, 0, tm=16)
    assert grouped_backend([(2048, 1408), (1408, 2048)], "pallas") == "pallas"
    assert grouped_backend([(2048, 1408), (1400, 2048)], "pallas") == "xla"
    assert grouped_backend([(2048, 1408)], "reference") == "xla"
    assert grouped_backend([(2048, 1408)]) == "xla"     # this process: a CPU


# ---- the expert layer through the kernel -------------------------------

def _layer_cfg(**kw):
    return ModelConfig.tiny(
        hidden_size=128, num_experts=8, num_experts_per_tok=3,
        expert_capacity_factor=0.0, moe_intermediate_size=128,
        moe_renormalize=False, num_layers=LAYERS, dtype="float32", **kw)


def _experts(cfg, int8):
    p = init_params(cfg, jax.random.PRNGKey(3), int8=int8)["layers"]
    router = jax.tree.map(lambda a: a[0], p["router"])
    w = router["weight"].astype(jnp.float32)
    if "scale" in router:
        w = w * router["scale"]
    return w, p["experts"]


@pytest.mark.parametrize("stacked", [False, True], ids=["layer", "stack"])
@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
def test_expert_layer_through_the_kernel_equals_it_through_ragged_dot(
        int8, stacked):
    cfg = _layer_cfg()
    router_w, stack = _experts(cfg, int8)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 128))
    mask = jnp.asarray([[True] * 33 + [False] * 7])
    x = x.at[0, 33:].set(jnp.nan)               # garbage in masked rows
    if stacked:
        args = dict(experts_p=None, stacked_experts=(stack, 1))
    else:
        args = dict(experts_p=jax.tree.map(lambda a: a[1], stack))
    with jax.default_matmul_precision("highest"):
        want, stats_x = moe_ffn(
            x, router_w, cfg=cfg, act=jax.nn.silu, token_mask=mask,
            return_stats=True, backend="reference", **args)
        got, stats_k = moe_ffn(
            x, router_w, cfg=cfg, act=jax.nn.silu, token_mask=mask,
            return_stats=True, backend="pallas", interpret=True, **args)
    assert np.isfinite(np.asarray(got[0, :33])).all()
    np.testing.assert_allclose(got[0, :33], want[0, :33], atol=2e-5)
    np.testing.assert_array_equal(stats_k, stats_x)
    assert np.asarray(stats_k)[1] == 33 * 3     # routed; none dropped


def test_tile_fill_ratio_against_a_count_by_hand():
    # 48 rows in tiles of 16; groups at rows 0-4, 5-13, 14-16, 17-30, 31-36
    rows, sizes, _ = SHAPES["decode"]
    plan = visit_plan(jnp.asarray(sizes, jnp.int32), rows, 16)
    # tiles touched: g1 {0}, g2 {0}, g4 {0, 1}, g6 {1}, g7 {1, 2}: 7 visits
    assert int(plan[3][0]) == 7
    np.testing.assert_array_equal(plan[1][:7], [1, 2, 4, 4, 6, 7, 7])
    np.testing.assert_array_equal(plan[2][:7], [0, 0, 0, 1, 1, 1, 2])
    # through the layer: one token 64 times, three experts of 64 rows each
    # in tiles of 128 (192 rows over 8 experts): rows 0-63 and 64-127 in
    # the first tile, 128-191 in the second, three visits
    cfg = _layer_cfg()
    router_w, stack = _experts(cfg, False)
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(5), (1, 1, 128)),
                 (1, 64, 1))
    _, stats = moe_ffn(x, router_w, None, cfg, jax.nn.silu,
                       return_stats=True, stacked_experts=(stack, 0))
    assert row_tile(192, 8) == 128
    assert np.asarray(stats)[4] == 192 / (3 * 128)
    # 61 of the 64 tokens masked: 3 rows an expert
    mask = jnp.asarray([[True] * 3 + [False] * 61])
    _, stats = moe_ffn(x, router_w, None, cfg, jax.nn.silu, token_mask=mask,
                       return_stats=True, stacked_experts=(stack, 0))
    dropped, routed, _, touched, fill, _ = np.asarray(stats)
    assert (dropped, routed, touched) == (0, 9, 3)
    # rows 0-2, 3-5, 6-8 all lie in the first tile: three visits to it
    assert fill == pytest.approx(9 / (3 * 128))
