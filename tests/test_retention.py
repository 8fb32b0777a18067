"""Power-retention decoders on the CPU at a small size, float32, seeded
weights: a matrix state a kv head in a per-slot pool, no page of KV, the
recurrence, the chunked form and the decode kernel (interpret mode).  The
oracle is the benchmark's plain reference
(``benchmark/lib/reference_retention_decoder.py``: the quadratic form
straight from the definition); the engine is compared by LOGITS."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import reference_retention_decoder as reference  # noqa: E402
from helix_tpu.engine.engine import (  # noqa: E402
    Engine, EngineConfig, Request, SamplingParams, UnsupportedForModel,
)
from helix_tpu.engine.kv_cache import CacheConfig, PagedKVCache  # noqa: E402
from helix_tpu.models.common import (  # noqa: E402
    BRUMBY_14B, CATALOG, ModelConfig,
)
from helix_tpu.models.llama import (  # noqa: E402
    forward, init_params, param_logical_axes, prefill_attn_fn,
)
from helix_tpu.ops import retention as R  # noqa: E402
from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry  # noqa: E402
from helix_tpu.ops.retention_kernel import (  # noqa: E402
    check_retention_geometry,
)

HF = dict(
    model_type="brumby", vocab_size=256, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, num_hidden_layers=3, rms_norm_eps=1e-6,
    rope_theta=1e6, max_position_embeddings=512, tie_word_embeddings=False,
)
# float32, the same mathematics through another order of operations (a state
# carried through chunks and single steps against one sum over the whole
# sequence): measured 3e-7 and under on logits of spread 0.16
TOL = 1e-5
# the least any fault reads at this size is the bf16 state's 2e-3
FAULT_LIMIT = 1e-3


def tiny(**kw):
    cfg = ModelConfig.from_hf_config(dict(HF, **kw), name="tiny-brumby")
    return dataclasses.replace(cfg, dtype="float32")


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, init_params(cfg, jax.random.PRNGKey(1))


def tokens_of(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, size=n).tolist()


def _engine(cfg, params, **kw):
    ecfg = EngineConfig(**{**dict(
        max_decode_batch=3, page_size=8, num_pages=96, max_pages_per_seq=16,
        max_prefill_len=16, attn_backend="reference",
        enable_prefix_cache=False), **kw})
    return Engine(cfg, params, ecfg)


def _req(rid, prompt, n=6, **kw):
    return Request(id=rid, prompt_tokens=prompt, sampling=SamplingParams(
        max_tokens=n, temperature=0.0, **kw))


def _run(eng, reqs, watch):
    """Step ``eng`` over ``reqs``; the watched request's next-token logits
    ``{tokens it had put out: logits [V]}``."""
    for r in reqs:
        eng.add_request(r)
    logits = {}
    while eng.has_work():
        eng.step()
        n = len(watch.output_tokens)
        if (n and n not in logits and watch.slot is not None
                and eng.slots[watch.slot] is watch):
            logits[n] = np.asarray(eng.next_token_logits()[watch.slot])
    return logits


def _rel(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))


def _draw(n, KVH=2, G=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (n, KVH * G, d)) * d ** -0.5,
            jax.random.normal(ks[1], (n, KVH, d)),
            jax.random.normal(ks[2], (n, KVH, d)),
            -jax.random.uniform(ks[3], (n, KVH), minval=1e-3, maxval=0.1))


# ---- the mixer's three forms ------------------------------------------------


@pytest.mark.parametrize("d", [16, 32, 128])
def test_phi_of_the_held_packing_is_the_square_of_the_dot_product(d):
    q, k = jax.random.normal(jax.random.PRNGKey(d), (2, 7, d))
    assert R.phi(q).shape == (7, R.held_rows(d))
    assert R.held_rows(d) == d * d // 2 + 4 * d
    assert R.held_rows(d) % R.tile_rows(d) == 0
    got = jnp.sum(R.phi(q) * R.phi(k), axis=-1)
    want = jnp.sum(q * k, axis=-1) ** 2
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-4)


def test_published_state_is_8704_rows_of_128_and_its_normaliser():
    assert R.held_rows(128) == 8704 and R.tile_rows(128) == 1088
    (s, sdt), (z, zdt) = BRUMBY_14B.state_arrays()
    assert (s, z) == ((8, 8704, 128), (8, 128, 128))
    assert sdt == zdt == "float32"
    with pytest.raises(ValueError, match="multiple of 16"):
        R.held_rows(24)


def test_recurrence_chunked_form_and_the_references_quadratic_form_agree():
    T, KVH, d = 40, 2, 16
    q, k, v, lg = _draw(T)
    want = reference.retention(q * d ** 0.5, k, v, lg, block=16)
    quad = R.retention_quadratic(q[None], k[None], v[None], lg[None])[0]
    S = jnp.zeros((1, KVH, R.held_rows(d), d))
    Z = jnp.zeros((1, KVH, d, d))
    rec = []
    for t in range(T):
        y, S, Z = R.retention_step(
            q[t:t + 1], k[t:t + 1], v[t:t + 1], lg[t:t + 1], S, Z)
        rec.append(y[0])
    at = jnp.arange(T)
    Sc, Zc, chunks = jnp.zeros_like(S[0]), jnp.zeros_like(Z[0]), 0
    for lo, hi in ((0, 13), (13, 14), (14, 40)):     # uneven, one of 1
        y, Sc, Zc = R.retention_chunk(
            q, k, v, lg, (at >= lo) & (at < hi), Sc, Zc)
        chunks = chunks + y
    for got in (quad, jnp.stack(rec), chunks):
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    # and the chunks leave the state the recurrence leaves
    np.testing.assert_allclose(Sc, S[0], atol=1e-5)
    np.testing.assert_allclose(Zc, Z[0], atol=1e-5)


def test_a_4096_token_sequence_does_not_drift():
    """Sixteen chunks of 256 through a float32 state, then single steps,
    against one sum over the whole sequence."""
    T, KVH, G, d = 4096 + 4, 1, 2, 16
    q, k, v, lg = _draw(T, KVH=KVH, G=G, seed=3)
    want = R.retention_quadratic(q[None], k[None], v[None], lg[None])[0]
    rows = jax.jit(R.retention_rows)
    pools = (jnp.zeros((1, 1, KVH, R.held_rows(d), d)),
             jnp.zeros((1, 1, KVH, d, d)))
    zero = jnp.zeros((1,), jnp.int32)
    for lo in range(0, 4096, 256):
        y, *pools = rows(
            q[lo:lo + 256], k[lo:lo + 256], v[lo:lo + 256], lg[lo:lo + 256],
            zero, zero + 256, zero + lo, zero, *pools, 0)
        assert float(jnp.max(jnp.abs(y - want[lo:lo + 256]))) < 2e-5
    S, Z = pools[0][0], pools[1][0]
    for t in range(4096, T):
        y, S, Z = R.retention_step(
            q[t:t + 1], k[t:t + 1], v[t:t + 1], lg[t:t + 1], S, Z)
        assert float(jnp.max(jnp.abs(y[0] - want[t]))) < 2e-5


def test_rows_share_a_flat_axis_and_each_writes_its_own_slot_alone():
    """Two rows packed on one axis beside padding: each reads as it does
    alone, a row that continues resumes from its slot, a row that starts
    starts from zeros whatever its slot held, and a slot no row names and
    the other layer are bit for bit what they were."""
    d, KVH = 16, 2
    q, k, v, lg = _draw(32, seed=5)
    shape = (2, 4, KVH, R.held_rows(d), d)
    S = jax.random.normal(jax.random.PRNGKey(9), shape)
    Z = jax.random.normal(jax.random.PRNGKey(10), (2, 4, KVH, d, d))
    i32 = lambda *x: jnp.asarray(x, jnp.int32)  # noqa: E731
    # row 0: tokens 0..11 continue slot 2; row 1: tokens 12..20 start slot 0;
    # a third row with no token; tokens 21..31 are padding
    y, S1, Z1 = R.retention_rows(
        q, k, v, lg, i32(0, 12, 21), i32(12, 9, 0), i32(5, 0, 0),
        i32(2, 0, 3), S, Z, 1)
    at = jnp.arange(32)
    ya, Sa, _ = R.retention_chunk(q, k, v, lg, at < 12, S[1, 2], Z[1, 2])
    yb, Sb, _ = R.retention_chunk(
        q, k, v, lg, (at >= 12) & (at < 21), 0 * S[1, 0], 0 * Z[1, 0])
    np.testing.assert_allclose(y, ya + yb, atol=1e-6)
    assert not np.any(np.asarray(y[21:]))
    np.testing.assert_allclose(S1[1, 2], Sa, atol=1e-6)
    np.testing.assert_allclose(S1[1, 0], Sb, atol=1e-6)
    for pool, was in ((S1, S), (Z1, Z)):
        assert np.array_equal(pool[0], was[0])
        assert np.array_equal(pool[1, 1], was[1, 1])
        assert np.array_equal(pool[1, 3], was[1, 3])
    # a row without a slot (warm-up) writes back what it read
    _, S2, Z2 = R.retention_rows(
        q, k, v, lg, i32(0), i32(12), i32(0), i32(2 ** 31 - 1), S, Z, 1)
    assert np.array_equal(S2, S) and np.array_equal(Z2, Z)


@pytest.mark.parametrize("live", [
    (True, False, True, True, False), (False,) * 5, (True,) * 5,
], ids=["some", "none", "all"])
def test_decode_kernel_in_interpret_mode_against_the_recurrence(live):
    B, KVH, G, d, L, N = 5, 2, 3, 16, 2, 6
    q, k, v, lg = _draw(B, KVH=KVH, G=G, seed=11)
    S = jax.random.normal(jax.random.PRNGKey(1), (L, N, KVH, R.held_rows(d), d))
    Z = jax.random.normal(jax.random.PRNGKey(2), (L, N, KVH, d, d))
    live = jnp.asarray(live)
    y0, S0, Z0 = R.retention_decode(q, k, v, lg, S, Z, 1, live,
                                    backend="reference")
    y1, S1, Z1 = R.retention_decode(q, k, v, lg, S, Z, 1, live,
                                    backend="pallas", interpret=True)
    np.testing.assert_allclose(S1, S0, atol=1e-5)
    np.testing.assert_allclose(Z1, Z0, atol=1e-5)
    idle = ~np.asarray(live)
    # idle slots, the slot past the rows and the other layer: bit for bit
    assert np.array_equal(S1[0], S[0])
    assert np.array_equal(S1[1][:B][idle], S[1][:B][idle])
    assert np.array_equal(S1[1, B:], S[1, B:])
    np.testing.assert_allclose(y1, y0, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("steps", [1, 2, 4, 8])
def test_a_fused_window_on_the_kernel_is_the_recurrence_a_step_at_a_time(
        steps):
    """A window of ``steps`` decode steps on the kernel in interpret mode,
    some rows live at only some steps and one at none, against the
    token-by-token recurrence: every step's outputs; the pool untouched
    until the last step; then the state and ``Z`` the recurrence leaves,
    every other slot and layer bit for bit, and the pending tokens empty."""
    B, KVH, G, d, L, N = 5, 2, 3, 16, 2, 6
    S = jax.random.normal(jax.random.PRNGKey(1), (L, N, KVH, R.held_rows(d), d))
    Z = jax.random.normal(jax.random.PRNGKey(2), (L, N, KVH, d, d))
    lives = np.random.default_rng(steps).random((steps, B)) < 0.6
    lives[:, 4] = False
    lives[-1, 0], lives[:, 1] = False, True     # one leaves early, one stays
    want, got = (S, Z), (S, Z)
    pending = R.window_zeros(B, KVH, d, 8)
    for i in range(steps):
        q, k, v, lg = _draw(B, KVH=KVH, G=G, seed=100 * steps + i)
        live = jnp.asarray(lives[i])
        y0, *want, _ = R.retention_window_step(
            q, k, v, lg, *want, None, 1, live, 0, True, backend="reference")
        y1, *got, pending = R.retention_window_step(
            q, k, v, lg, *got, pending, 1, live, jnp.int32(i),
            jnp.asarray(i == steps - 1), backend="pallas", interpret=True)
        np.testing.assert_allclose(y1, y0, rtol=1e-4, atol=1e-4)
        assert not np.any(np.asarray(y1)[~lives[i]])
        if i < steps - 1:
            assert np.array_equal(got[0], S)
            assert np.array_equal(pending[3], lives[:i + 1].any(axis=0))
    np.testing.assert_allclose(got[0], want[0], atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)
    idle = ~lives.any(axis=0)
    for pool, was in ((got[0], S), (got[1], Z)):
        assert np.array_equal(pool[0], was[0])
        assert np.array_equal(pool[1][:B][idle], was[1][:B][idle])
        assert np.array_equal(pool[1, B:], was[1, B:])
    assert not any(np.any(np.asarray(a)) for a in pending)


def _recurrence(q, k, v, lg, S0, Z0):
    """The token-by-token recurrence over one row from ``(S0, Z0)``."""
    def step(state, x):
        y, S, Z = R.retention_step(*(a[None] for a in x), *state)
        return (S, Z), y[0]

    (S, Z), y = jax.lax.scan(step, (S0[None], Z0[None]), (q, k, v, lg))
    return y, S[0], Z[0]


NO_SLOT = 2 ** 31 - 1
# (tokens on the axis, t0, qlen, hist, slots) over a pool of 5 slots
CHUNK_PLANS = {
    "a_row_from_zeros": (128, (0,), (128,), (0,), (3,)),
    "a_row_from_a_state": (128, (0,), (128,), (77,), (3,)),
    # uneven rows back to back, two of them in one block of 128 tokens, one
    # across a block's edge, history on some; then a row with no token
    "a_wave": (256, (0, 12, 150, 180), (12, 138, 30, 0), (5, 0, 7, 0),
               (2, 0, 4, 3)),
    "a_row_without_a_slot": (32, (0,), (20,), (0,), (NO_SLOT,)),
    "padding_and_idle_rows": (64, (0, 9, 9), (9, 0, 0), (4, 0, 3),
                              (1, 0, NO_SLOT)),
    "a_row_from_zeros_in_a_slot_of_nan": (
        200, (0, 150), (150, 50), (0, 9), (3, 1)),
    "tokens_that_are_no_multiple_of_the_block": (
        200, (0, 131), (131, 69), (40, 0), (4, 2)),
}


@pytest.mark.parametrize("case", sorted(CHUNK_PLANS))
def test_chunk_kernel_in_interpret_mode_against_both_references(case):
    """``retention_rows`` on the kernel path (``retention_chunk_tpu`` in
    interpret mode) against ``retention_chunk`` a row and against the
    token-by-token recurrence: outputs, the state each row's slot is left
    with, and every other slot and layer bit for bit."""
    T, t0, qlen, hist, slots = CHUNK_PLANS[case]
    d, KVH, N = 16, 2, 5
    q, k, v, lg = _draw(T, seed=len(case))
    # a token's own score is kept away from 0: where a row's first scores
    # are near eps the RECURRENCE is ill-conditioned (its numerator goes
    # through phi, its normaliser does not), and no form agrees with it
    kq = jnp.repeat(k, q.shape[1] // KVH, axis=1)
    q = q + kq / jnp.linalg.norm(kq, axis=-1, keepdims=True)
    S = jax.random.normal(
        jax.random.PRNGKey(9), (2, N, KVH, R.held_rows(d), d))
    Z = jax.random.normal(jax.random.PRNGKey(10), (2, N, KVH, d, d))
    if "nan" in case:
        S, Z = S.at[:, 3].set(jnp.nan), Z.at[:, 3].set(jnp.nan)
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    y, S1, Z1 = jax.jit(
        R.retention_rows, static_argnames=("backend", "interpret"))(
        q, k, v, lg, i32(t0), i32(qlen), i32(hist), i32(slots), S, Z, 1,
        backend="pallas", interpret=True)
    at = jnp.arange(T)
    written, owned = set(), np.zeros(T, bool)
    for lo, n, h, slot in zip(t0, qlen, hist, slots):
        if not n:
            continue
        held = slot < N
        keep = held and h > 0
        S0 = S[1, slot] if keep else jnp.zeros_like(S[1, 0])
        Z0 = Z[1, slot] if keep else jnp.zeros_like(Z[1, 0])
        row = slice(lo, lo + n)
        owned[row] = True
        yc, Sc, Zc = R.retention_chunk(
            q, k, v, lg, (at >= lo) & (at < lo + n), S0, Z0)
        yr, Sr, Zr = _recurrence(q[row], k[row], v[row], lg[row], S0, Z0)
        assert np.isfinite(np.asarray(y[row])).all()
        np.testing.assert_allclose(y[row], yc[row], rtol=5e-5, atol=2e-5)
        np.testing.assert_allclose(y[row], yr, rtol=5e-5, atol=2e-5)
        if held:
            written.add(slot)
            for got, chunked, stepped in ((S1, Sc, Sr), (Z1, Zc, Zr)):
                np.testing.assert_allclose(
                    got[1, slot], chunked, rtol=5e-5, atol=2e-5)
                np.testing.assert_allclose(
                    got[1, slot], stepped, rtol=5e-5, atol=2e-5)
    assert not np.any(np.asarray(y)[~owned])
    for pool, was in ((S1, S), (Z1, Z)):
        pool, was = np.asarray(pool), np.asarray(was)
        assert np.array_equal(pool[0], was[0], equal_nan=True)
        for slot in set(range(N)) - written:
            assert np.array_equal(pool[1, slot], was[1, slot], equal_nan=True)


def test_kernel_geometry_mosaic_refuses_is_refused_by_name():
    check_retention_geometry(40, 8, 128)
    with pytest.raises(UnsupportedKernelGeometry, match="128 lanes"):
        check_retention_geometry(4, 2, 16)
    with pytest.raises(UnsupportedKernelGeometry, match="divide"):
        check_retention_geometry(40, 7, 128)
    # the chunked form's kernel path refuses what the decode kernel does
    q, k, v, lg = _draw(16)
    one = jnp.ones((1,), jnp.int32)
    with pytest.raises(UnsupportedKernelGeometry, match="128 lanes"):
        R.retention_rows(
            q, k, v, lg, 0 * one, 16 * one, 0 * one, one,
            jnp.zeros((1, 2, 2, R.held_rows(16), 16)),
            jnp.zeros((1, 2, 2, 16, 16)), 0, backend="pallas")


# ---- the model --------------------------------------------------------------


def test_config_reads_the_published_keys(model):
    cfg, params = model
    assert cfg.mixers == ("retention",) * 3 and cfg.qk_norm
    assert (cfg.num_attn_layers, cfg.num_retention_layers,
            cfg.num_state_layers, cfg.state_mixer) == (0, 3, 3, "retention")
    assert cfg.retention_degree == 2 and not cfg.tie_word_embeddings
    (group,) = cfg.layer_runs()
    assert (group.reps, [r.mixer for r in group.runs]) == (1, ["retention"])
    big = CATALOG["manifestai/Brumby-14B-Base"]
    assert big is BRUMBY_14B and big.num_retention_layers == 40
    assert (big.hidden_size, big.num_heads, big.num_kv_heads, big.head_dim,
            big.intermediate_size, big.vocab_size) == (
        5120, 40, 8, 128, 17408, 151936)
    lp = params["run00"]
    assert lp["g_proj"]["weight"].shape == (3, 64, 2)
    bias = np.asarray(lp["g_bias"]["bias"])
    assert bias.shape == (3, 2) and bias.min() >= 3 and bias.max() <= 7
    axes = param_logical_axes(cfg)["run00"]
    assert set(axes) == set(lp)
    with pytest.raises(ValueError, match="one kind of recurrent state"):
        dataclasses.replace(
            cfg, layer_types=("conv", "retention", "attn")).state_mixer
    with pytest.raises(ValueError, match="degree 3"):
        dataclasses.replace(cfg, retention_degree=3).state_arrays()


def test_int8_tree_has_the_float_trees_structure(model):
    cfg, params = model
    q = init_params(cfg, jax.random.PRNGKey(1), int8=True)
    assert q["run00"]["g_proj"]["weight"].dtype == jnp.int8
    assert q["run00"]["g_bias"]["bias"].dtype == jnp.float32
    assert set(q["run00"]) == set(params["run00"])


def test_forward_without_a_cache_is_the_reference(model):
    cfg, params = model
    toks = jnp.asarray(tokens_of(50, 2))
    got, kv = forward(params, cfg, toks[None], jnp.arange(50)[None],
                      attn_fn=prefill_attn_fn)
    assert kv is None         # no layer has pages: nothing fresh to scatter
    want = reference.forward(params, HF, toks, block=16)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < TOL
    # a block of layers from a hidden state, and rows of the head
    h = reference.forward(params, HF, toks, layers=(0, 2), head=False)
    rows = reference.forward(params, HF, toks, layers=(2, 3), h=h,
                             rows=[10, 49])
    assert np.abs(np.asarray(rows) - np.asarray(want)[[10, 49]]).max() < TOL


# ---- the cache --------------------------------------------------------------


def test_page_pool_holds_no_bytes_and_the_state_pool_follows_the_kind():
    cc = CacheConfig(num_pages=64, page_size=16, state_slots=4)
    m = dataclasses.replace(BRUMBY_14B, num_layers=10,
                            layer_types=("retention",) * 10)
    assert cc.page_bytes(m) == 0
    assert cc.state_shapes(m) == (
        ((10, 4, 8, 8704, 128), "float32"), ((10, 4, 8, 128, 128), "float32"))
    assert cc.state_shape(m) == (10, 4, 8, 8704, 128)
    per_slot = 10 * 8 * (8704 + 128) * 128 * 4
    assert cc.state_bytes(m) == cc.total_bytes(m) == 4 * per_slot
    # the budget buys slots, not pages; pages stay the bookkeeping
    fit = CacheConfig.fit_hbm(m, 8 * 10 ** 9, max_pages_per_seq=160,
                              state_slots=24)
    assert fit.state_slots == 8 * 10 ** 9 // per_slot == 22
    assert fit.num_pages == 22 * 160 + 1
    assert CacheConfig.fit_hbm(m, 10 ** 10, state_slots=24).state_slots == 24
    cfg = tiny()
    cache = PagedKVCache.create(cfg, CacheConfig(
        num_pages=8, page_size=8, state_slots=2))
    assert cache.k_pages.shape == (0, 8, 8, 2, 16) and cache.k_pages.size == 0
    S, Z = cache.state
    assert S.shape == (3, 2, 2, 192, 16) and Z.shape == (3, 2, 2, 16, 16)
    assert S.dtype == Z.dtype == jnp.float32


# ---- the engine -------------------------------------------------------------


def _chunked_prefill_then_decode(eng, params):
    """A 37-token prompt in three chunks beside a second request (mixed
    steps), then decode steps.  Returns the watched request's next-token
    logits at every step, the reference's, the sequence and the rows."""
    prompt = tokens_of(37, 0)
    req, other = _req("a", prompt, 7), _req("b", tokens_of(11, 1), 9)
    got = _run(eng, [req, other], req)
    assert len(got) >= 6 and eng.num_mixed_steps >= 1
    assert eng.mixer_counts["chunk_rows"] >= 4
    assert 2 <= eng.mixer_counts["chunk_rows_from_zeros"] < (
        eng.mixer_counts["chunk_rows"])
    seq = jnp.asarray(prompt + req.output_tokens)
    at = [len(prompt) + n - 1 for n in sorted(got)]
    mine = np.stack([got[n] for n in sorted(got)])
    want = np.asarray(reference.forward(params, HF, seq, rows=at, block=16))
    assert np.abs(mine - want).max() < TOL
    return mine, want, seq, at


def test_chunked_prefill_then_decode_through_the_state_is_the_reference(model):
    """Next-token logits against the reference's full forward at every
    step, and each fault over the limit at every step."""
    cfg, params = model
    eng = _engine(cfg, params)
    mine, want, seq, at = _chunked_prefill_then_decode(eng, params)
    assert eng.mixer_counts["decode_rows"] >= 12
    per_slot = eng.recurrent_state_bytes // 3
    assert eng.mixer_counts["state_bytes_touched"] == 2 * per_slot * (
        eng.mixer_counts["decode_rows"] + eng.mixer_counts["chunk_rows"])
    for kw in (dict(state_bf16=True), dict(gate=False),
               dict(normaliser=False), dict(cross_sqrt2=False),
               dict(zero_state_at=32)):
        bad = np.asarray(reference.forward(
            params, HF, seq, rows=at, block=16, **kw))
        least = min(_rel(b, w) for b, w in zip(bad, want))
        assert least > FAULT_LIMIT, (kw, least)
        assert max(_rel(m, w) for m, w in zip(mine, want)) < least / 100


def test_chunked_prefill_then_decode_on_the_kernel_path(model, monkeypatch):
    """The same prompt in three chunks beside a second request, then decode
    steps, with the engine on ``backend="pallas"``: the chunk kernel and the
    decode kernel in interpret mode (the test steers that, and lets width 16
    past the geometry only Mosaic refuses), against the reference's full
    forward at every step."""
    import functools

    from helix_tpu.ops import retention_kernel

    cfg, params = model
    monkeypatch.setattr(
        retention_kernel, "check_retention_geometry", lambda *a: None)
    for name in ("retention_rows", "retention_window_step"):
        monkeypatch.setattr(
            R, name, functools.partial(getattr(R, name), interpret=True))
    _chunked_prefill_then_decode(
        _engine(cfg, params, attn_backend="pallas"), params)


def _windows_a_chunked_prompt_and_a_reused_slot(eng):
    import joint_pass

    return joint_pass.windows_a_chunked_prompt_and_a_reused_slot(
        eng, _req, tokens_of)


def test_fused_windows_on_the_kernel_path_give_the_references_tokens(
        model, monkeypatch):
    """Through the engine with windows of up to 4 fused decode steps on
    ``backend="pallas"`` (the kernels in interpret mode): steps that read the
    state and write nothing, commits, chunk rows and decode rows that
    continue from what a window committed, and a reused slot, against the
    plain recurrence a step at a time (``backend="reference"``, no window)."""
    import functools

    from helix_tpu.ops import retention_kernel

    cfg, params = model
    want = _windows_a_chunked_prompt_and_a_reused_slot(_engine(cfg, params))
    monkeypatch.setattr(
        retention_kernel, "check_retention_geometry", lambda *a: None)
    for name in ("retention_rows", "retention_window_step"):
        monkeypatch.setattr(
            R, name, functools.partial(getattr(R, name), interpret=True))
    eng = _engine(cfg, params, attn_backend="pallas", decode_steps_per_sync=4,
                  adaptive_sync_max_streams=0)
    got = _windows_a_chunked_prompt_and_a_reused_slot(eng)
    assert got == want and all(got.values())
    counts = eng.mixer_counts
    # windows were fused: a good part of the decode row-steps wrote nothing
    assert counts["state_writes"] < 0.75 * counts["decode_rows"]
    assert counts["chunk_rows"] >= 5 and eng.num_mixed_steps >= 1


def test_a_window_of_four_over_three_rows_writes_the_state_three_times(model):
    """The host's account of one fused launch: 12 decode row-steps, 3 writes
    of the state (``helix_retention_state_writes_total``, and
    ``retention_state_writes`` on the launch's span), and the bytes that
    moved: ``S`` read at every step and written once a row, ``Z`` read and
    written at every step."""
    import joint_pass

    cfg, params = model
    eng = _engine(cfg, params, decode_steps_per_sync=4)
    for i in range(3):
        eng.add_request(_req(str(i), tokens_of(5 + i, i), 12))
    while eng.waiting or eng._decode_window() != 4:
        eng.step()
    before = dict(eng.mixer_counts)
    with joint_pass.launch_spans() as seen:
        eng.step()
    added = {k: n - before[k] for k, n in eng.mixer_counts.items()}
    assert added["decode_rows"] == 12 and added["state_writes"] == 3
    assert [kw["retention_state_writes"] for kw in seen] == [3]
    (s, _), (z, _) = cfg.state_arrays()
    s, z = (cfg.num_state_layers * int(np.prod(a)) * 4 for a in (s, z))
    assert added["state_bytes_touched"] == 12 * (s + z) + 12 * z + 3 * s
    # a step that stands alone writes what it reads
    eng2 = _engine(cfg, params)
    one = _req("x", tokens_of(5), 4)
    _run(eng2, [one], one)
    assert eng2.mixer_counts["state_writes"] == (
        eng2.mixer_counts["decode_rows"])


def test_a_mixed_step_gives_each_row_what_it_gets_alone(model):
    cfg, params = model
    prompt, short = tokens_of(40, 4), tokens_of(9, 5)
    eng = _engine(cfg, params)
    req = _req("a", prompt)
    both = _run(eng, [_req("s", short, 12), req], req)
    assert eng.num_mixed_steps >= 1
    solo = _engine(cfg, params)
    ref = _req("a", prompt)
    alone = _run(solo, [ref], ref)
    shared = sorted(set(both) & set(alone))
    assert len(shared) >= 4
    for n in shared:
        assert np.abs(both[n] - alone[n]).max() < TOL


def _decoding(model):
    cfg, params = model
    eng = _engine(cfg, params)
    eng.add_request(_req("d", tokens_of(7, 3), 40, seed=11))
    eng.step()
    eng.step()
    return eng


def test_a_chunk_and_the_decode_rows_share_one_pass(model):
    """One pass a program: the projections and the MLP once, the chunked
    form for the prefill rows and the recurrence for the state rows inside
    the one mixer call."""
    import joint_pass

    eng = _decoding(model)
    joint_pass.assert_one_forward(eng, 16, 1, "dot_general", "mlp.down")
    joint_pass.assert_one_forward(
        eng, 16, 1, "dot_general", "retention.out_proj")


def test_a_chunk_beside_decode_rows_is_the_chunk_then_the_decode_step(model):
    import joint_pass

    cfg, params = model

    def reqs():
        return [_req("s", tokens_of(9, 5), 14), _req("x", tokens_of(40, 4))]

    joint_pass.assert_mixed_is_chunk_then_decode(
        lambda **kw: _engine(cfg, params, **kw), reqs, "x", TOL)


def test_a_wave_of_inert_rows_leaves_the_decode_state_and_the_pool(model):
    import joint_pass

    eng = _decoding(model)
    before = [np.asarray(a) for a in eng.cache.state]
    joint_pass.assert_inert_wave_keeps_decode_state(eng, 16)
    for a, b in zip(before, eng.cache.state):
        assert np.array_equal(a, np.asarray(b))
    assert before[0].any()


def test_a_wave_beside_running_rows_is_the_wave_then_the_decode_step(model):
    """The running rows' matrix states take one recurrence step inside the
    wave's pass; the row out of headroom keeps its state bit for bit."""
    import joint_pass

    cfg, params = model

    def reqs():
        return ([_req("a", tokens_of(7, 3), 12, seed=11),
                 _req("g", tokens_of(5, 4), 9)],
                _req("short", tokens_of(6, 5), 2),
                _req("late", tokens_of(11, 6), 8))

    joint_pass.assert_wave_is_wave_then_decode(
        lambda: _engine(cfg, params, max_decode_batch=4), reqs, TOL)


def test_a_reused_slot_starts_from_zeros(model):
    """One slot, two requests one after the other: the second reads what it
    reads on a fresh engine, not the state the first left."""
    cfg, params = model
    eng = _engine(cfg, params, max_decode_batch=1)
    first, second = _req("x", tokens_of(21, 6)), _req("y", tokens_of(19, 7))
    _run(eng, [first], first)
    assert float(jnp.max(jnp.abs(eng.cache.state[0]))) > 0
    got = _run(eng, [second], second)
    fresh_req = _req("y", tokens_of(19, 7))
    fresh = _run(_engine(cfg, params, max_decode_batch=1), [fresh_req],
                 fresh_req)
    assert second.output_tokens == fresh_req.output_tokens
    for n in got:
        assert np.abs(got[n] - fresh[n]).max() < TOL


def test_idle_slots_and_padding_leave_the_pool_bit_for_bit(model):
    cfg, params = model
    eng = _engine(cfg, params)
    req = _req("a", tokens_of(13, 8), 5)      # 13 tokens in a rung of 16
    eng.add_request(req)
    before = None
    while eng.has_work():
        eng.step()
        S, Z = (np.asarray(a) for a in eng.cache.state)
        if before is not None and req.slot is not None:
            idle = [i for i in range(3) if i != req.slot]
            assert np.array_equal(S[:, idle], before[0][:, idle])
            assert np.array_equal(Z[:, idle], before[1][:, idle])
        before = (S, Z)
    assert before is not None and np.any(before[0])
    idle = [i for i in range(3) if i != 0]
    assert not np.any(before[0][:, idle]) and not np.any(before[1][:, idle])


REFUSED_SETTINGS = {
    "int8_kv": (dict(kv_cache_dtype="int8"), "kv_cache_dtype int8"),
    "adapters": (dict(adapter_pool_slots=2), "adapter_pool_slots"),
    "speculation": (dict(enable_spec_decode=True), "enable_spec_decode"),
    "host_tier": (dict(host_pool_bytes=1 << 20), "host_pool_bytes"),
    "tiered": (dict(ctx_hot_pages=2, host_pool_bytes=1 << 20),
               "ctx_hot_pages"),
    "prefix_cache": (dict(enable_prefix_cache=True), "enable_prefix_cache"),
}


@pytest.mark.parametrize("name", sorted(REFUSED_SETTINGS))
def test_what_cannot_carry_a_matrix_state_is_refused_by_name(model, name):
    cfg, params = model
    kw, setting = REFUSED_SETTINGS[name]
    with pytest.raises(UnsupportedForModel) as e:
        _engine(cfg, params, **kw)
    assert "a matrix state (power retention)" in str(e.value)
    assert setting in str(e.value) and cfg.name in str(e.value)


def test_a_mesh_is_refused_by_name(model):
    from helix_tpu.engine.engine import refuse_unsupported

    class TwoDevices:
        class devices:
            size = 2

    cfg, _ = model
    with pytest.raises(UnsupportedForModel, match="a mesh of more than one"):
        refuse_unsupported(cfg, EngineConfig(
            max_decode_batch=2, enable_prefix_cache=False), TwoDevices())


@pytest.mark.parametrize("call", ["export_request", "export_prefill",
                                  "import_request", "kv_filestore"])
def test_calls_that_move_pages_are_refused_by_name(model, call):
    cfg, params = model
    eng = _engine(cfg, params)
    with pytest.raises(UnsupportedForModel, match="a matrix state"):
        if call == "kv_filestore":
            eng.kv_filestore = object()
        elif call == "import_request":
            eng.import_request(None)
        else:
            getattr(eng, call)("nobody")


def test_conv_models_keep_their_prefix_cache(model):
    """The new refusal names the matrix state alone: a model whose state is
    two vectors a layer still files and resumes snapshots."""
    cfg = ModelConfig.tiny(
        vocab_size=256, dtype="float32", num_layers=3,
        layer_types=("conv", "attn", "conv"), conv_kernel=3)
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)), EngineConfig(
        max_decode_batch=2, page_size=8, num_pages=64, max_pages_per_seq=16,
        max_prefill_len=16, attn_backend="reference"))
    assert eng.prefix_cache is not None and eng.prefix_cache.stateful
    assert eng.cache.state.shape == (2, 2, 2, 64)


def test_launch_record_and_metrics_carry_the_retention_layers(model):
    import joint_pass

    cfg, params = model
    eng = _engine(cfg, params)
    with joint_pass.launch_spans() as seen:
        req = _req("a", tokens_of(9, 9), 3)
        _run(eng, [req], req)
    assert seen and all(
        kw["retention_layers"] == 3 and kw["attn_layers"] == 0
        and "conv_layers" not in kw for kw in seen)
    assert eng.recurrent_state_bytes == 3 * 3 * 2 * (192 + 16) * 16 * 4
    # the one prompt is one chunk row, and it starts its sequence
    assert sum(kw["retention_chunk_rows"] for kw in seen) == 1
    assert sum(kw["retention_chunk_rows_from_zeros"] for kw in seen) == 1
    assert eng.mixer_counts["chunk_rows_from_zeros"] == 1
    # a 40-token prompt in three chunks: one row of three starts it
    long = _req("b", tokens_of(40, 2), 2)
    _run(eng, [long], long)
    assert eng.mixer_counts["chunk_rows"] == 4
    assert eng.mixer_counts["chunk_rows_from_zeros"] == 2


def test_metrics_and_flight_records_carry_the_rows_from_zeros(model):
    """``helix_retention_chunk_rows_from_zeros_total`` beside
    ``helix_retention_rows_total{kind="chunk"}`` on ``/metrics``, and both
    as a step's deltas in its flight record."""
    import threading

    from helix_tpu.serving.engine_loop import EngineLoop
    from helix_tpu.serving.openai_api import OpenAIServer
    from helix_tpu.serving.registry import ModelRegistry, ServedModel
    from helix_tpu.serving.tokenizer import ByteTokenizer

    cfg, params = model
    eng = _engine(cfg, params)
    loop = EngineLoop(eng, "tiny-brumby")        # never started: inline
    done = threading.Event()
    loop.submit(_req("m", tokens_of(37, 3), 4),
                lambda e: done.set() if e.finished else None)
    for _ in range(200):
        if done.is_set():
            break
        assert loop._pass()
    assert done.is_set()
    records = loop.flight.snapshot()["recent"]
    # a 37-token prompt in chunks of 16: three rows, the first from zeros
    assert sum(r["retention_chunk_rows"] for r in records) == 3
    assert sum(r["retention_chunk_rows_from_zeros"] for r in records) == 1
    registry = ModelRegistry()
    registry.register(ServedModel(
        name="tiny-brumby", loop=loop, tokenizer=ByteTokenizer(),
        context_length=128))
    text = OpenAIServer(registry).obs.render()

    def value(series, label=""):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(series) and label in ln)
        return float(line.rsplit(" ", 1)[1])

    assert value("helix_retention_rows_total{", 'kind="chunk"') == 3
    assert value("helix_retention_chunk_rows_from_zeros_total{") == 1
