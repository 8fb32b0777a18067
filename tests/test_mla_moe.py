"""DeepSeek-V2-style decoders on the CPU at a small size, float32, seeded
weights: latent attention (MLA) with its paged latent cache and kernel,
YaRN rope, the dropless grouped expert layer with shared experts, the
two-kind layer stack.  The oracle is the benchmark's plain reference
(``benchmark/lib/reference_mla_moe_decoder.py``)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import reference_mla_moe_decoder as reference  # noqa: E402
from helix_tpu.engine.engine import (  # noqa: E402
    Engine, EngineConfig, Request, SamplingParams, UnsupportedForModel,
)
from helix_tpu.engine.kv_cache import (  # noqa: E402
    CacheConfig, PagedKVCache, gather_pages, restore_pages, write_kv,
)
from helix_tpu.models.common import (  # noqa: E402
    CATALOG, DEEPSEEK_V2_LITE, ModelConfig,
)
from helix_tpu.models.llama import (  # noqa: E402
    forward, init_params, mla_absorbed_weights, mla_softmax_scale,
    param_logical_axes, prefill_attn_fn,
)
from helix_tpu.models.mixers import latent_widths  # noqa: E402
from helix_tpu.models.moe import moe_ffn, route  # noqa: E402
from helix_tpu.ops import rope as rope_ops  # noqa: E402
from helix_tpu.ops.paged import (  # noqa: E402
    mla_attention_reference, mla_ragged_paged_attention_reference,
)

YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}


def tiny(**kw):
    base = dict(
        vocab_size=300, hidden_size=64, num_layers=3, num_heads=4,
        num_kv_heads=4, head_dim=24, intermediate_size=96,
        rope_theta=10000.0, rope_scaling=tuple(sorted(YARN.items())),
        rms_norm_eps=1e-6, dtype="float32", max_position_embeddings=512,
        num_experts=8, num_experts_per_tok=3, expert_capacity_factor=0.0,
        moe_intermediate_size=32, num_shared_experts=2, first_k_dense=1,
        moe_renormalize=False, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, name="tiny-mla-moe",
    )
    base.update(kw)
    return ModelConfig(**base)


def hf_of(cfg):
    """The Hugging Face keys the reference reads, from a ModelConfig."""
    return {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": dict(cfg.rope_scaling or ()),
        "first_k_dense_replace": cfg.first_k_dense,
        "n_routed_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.moe_renormalize,
    }


def tokens_of(n, seed=0, vocab=300):
    return np.random.default_rng(seed).integers(1, vocab, size=n).tolist()


# ---- the model against the plain reference -------------------------------

@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
def test_forward_agrees_with_the_reference(int8):
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(1), int8=int8)
    toks = jnp.asarray([tokens_of(24)], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, toks, jnp.arange(24)[None],
                         attn_fn=prefill_attn_fn)
    want = reference.forward(params, hf_of(cfg), toks[0])
    # float32 both sides, the same mathematics (absorbed against
    # decompressed attention, a grouped product against a loop over
    # experts): rounding only.  bf16 anywhere would miss by 1e-2.
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-5


def test_reference_in_blocks_of_layers_is_the_full_forward():
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(2))
    toks = jnp.asarray(tokens_of(12), jnp.int32)
    hf = hf_of(cfg)
    h = reference.forward(params, hf, toks, layers=(0, 2), head=False)
    got = reference.forward(params, hf, toks, layers=(2, 3), h=h)
    want = reference.forward(params, hf, toks)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-6


def _engine(cfg, params, **kw):
    ecfg = EngineConfig(
        max_decode_batch=kw.pop("slots", 2), page_size=8, num_pages=64,
        max_pages_per_seq=16, max_prefill_len=16, attn_backend="reference",
        **kw)
    return Engine(cfg, params, ecfg)


def test_chunked_prefill_then_decode_through_the_latent_cache():
    """A 40-token prompt prefills in three chunks of 16 (the second and
    third attend the paged latent history), then every decode step's
    logits, read through the cache, against the reference's full forward
    over the same tokens."""
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(3))
    eng = _engine(cfg, params)
    prompt = tokens_of(40, seed=1)
    req = Request(id="a", prompt_tokens=prompt,
                  sampling=SamplingParams(max_tokens=8, temperature=0.0))
    eng.add_request(req)
    hf = hf_of(cfg)
    worst = 0.0
    steps = 0
    with jax.default_matmul_precision("highest"):
        while eng.has_work():
            eng.step()
            if not req.output_tokens or req.slot is None:
                continue
            if eng.slots[req.slot] is not req:
                break
            seq = jnp.asarray(prompt + req.output_tokens, jnp.int32)
            got = np.asarray(eng.next_token_logits()[req.slot])
            want = np.asarray(reference.forward(params, hf, seq)[-1])
            worst = max(worst, np.abs(got - want).max())
            steps += 1
    assert steps >= 6
    # float32, same mathematics through another order of operations
    assert worst < 2e-5, worst
    assert eng.moe_dropped_tokens == 0
    assert eng.moe_routed_tokens > 0 and eng.moe_experts_touched > 0


def _decoding(cfg, params, **kw):
    """An engine with one sequence two decode steps in."""
    eng = _engine(cfg, params, **kw)
    eng.add_request(Request(
        id="d", prompt_tokens=tokens_of(7, seed=3),
        sampling=SamplingParams(max_tokens=40, temperature=0.8, seed=11,
                                frequency_penalty=0.3)))
    eng.step()
    eng.step()
    return eng


def test_a_chunk_and_the_decode_rows_share_one_grouped_product():
    """A program with a prefill segment holds ONE pass: the expert layers'
    three grouped products once (the two-call form held them twice), and
    the MoE step stats are that one product's."""
    import joint_pass

    cfg = tiny()
    eng = _decoding(cfg, init_params(cfg, jax.random.PRNGKey(3)))
    joint_pass.assert_one_forward(eng, 16, 1, "ragged_dot_general", "moe.experts")
    joint_pass.assert_one_forward(eng, 16, 1, "dot_general", "moe.shared")
    routed = eng.moe_routed_tokens
    eng._ragged_step("mixed", plan=joint_pass.dummy_plan(eng, 16, 1),
                     draft_len=eng._zero_rows, n_extra=0)
    jax.block_until_ready(eng.cache.k_pages)
    eng._drain_moe_drops()
    # 16 prefill tokens and one live decode row, k choices each, a layer
    k, layers = cfg.num_experts_per_tok, cfg.num_moe_layers
    assert eng.moe_routed_tokens - routed == 17 * k * layers
    assert eng.moe_dropped_tokens == 0


def test_a_chunk_beside_decode_rows_is_the_chunk_then_the_decode_step():
    import joint_pass

    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(3))

    def reqs():
        return [Request(id=rid, prompt_tokens=tokens_of(n, seed=sd),
                        sampling=SamplingParams(max_tokens=m,
                                                temperature=0.0))
                for rid, n, sd, m in (("s", 6, 5, 14), ("x", 41, 1, 6))]

    with jax.default_matmul_precision("highest"):
        joint_pass.assert_mixed_is_chunk_then_decode(
            lambda **kw: _engine(cfg, params, **kw), reqs, "x", 2e-5)


def test_a_wave_of_inert_rows_leaves_the_decode_state_bit_for_bit():
    import joint_pass

    cfg = tiny()
    joint_pass.assert_inert_wave_keeps_decode_state(
        _decoding(cfg, init_params(cfg, jax.random.PRNGKey(3))), 16)


def test_a_wave_beside_running_rows_is_the_wave_then_the_decode_step():
    """The running rows' one-token rows go through the latent kernel and
    the grouped product beside the wave's prompt: the streams of the wave
    alone followed by the decode step alone."""
    import joint_pass

    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(3))

    def reqs():
        def req(rid, n, sd, m, **kw):
            return Request(id=rid, prompt_tokens=tokens_of(n, seed=sd),
                           sampling=SamplingParams(max_tokens=m, **kw))
        return ([req("a", 7, 3, 12, temperature=0.8, seed=11,
                     frequency_penalty=0.3),
                 req("g", 5, 4, 9, temperature=0.0)],
                req("short", 6, 5, 2, temperature=0.0),
                req("late", 11, 6, 8, temperature=0.0))

    with jax.default_matmul_precision("highest"):
        joint_pass.assert_wave_is_wave_then_decode(
            lambda: _engine(cfg, params, slots=4), reqs, 2e-5)


def test_padding_rows_and_idle_slots_change_no_live_rows_logits():
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(4))
    prompt = tokens_of(20, seed=2)

    def run(slots, others):
        eng = _engine(cfg, params, slots=slots)
        reqs = [Request(id="x", prompt_tokens=prompt,
                        sampling=SamplingParams(max_tokens=4,
                                                temperature=0.0))]
        for i, n in enumerate(others):
            reqs.append(Request(
                id=f"o{i}", prompt_tokens=tokens_of(n, seed=9 + i),
                sampling=SamplingParams(max_tokens=2, temperature=0.0)))
        for r in reqs:
            eng.add_request(r)
        logits = []
        while eng.has_work():
            eng.step()
            r = reqs[0]
            if r.output_tokens and r.slot is not None and (
                    eng.slots[r.slot] is r):
                logits.append(np.asarray(eng.next_token_logits()[r.slot]))
        return reqs[0].output_tokens, logits

    alone, la = run(1, [])
    crowded, lc = run(4, [5, 11])
    assert alone == crowded
    # batch-mates, idle slots and bucket padding are never routed and
    # never attended: what is left is the reduction order of other shapes
    assert max(np.abs(a - b).max() for a, b in zip(la, lc)) < 2e-5


# ---- attention ------------------------------------------------------------

def test_absorbed_attention_is_the_decompressed_attention():
    cfg = tiny()
    rng = np.random.default_rng(0)
    S, H, R = 10, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    q_nope, q_pe, c, k_pe = f(S, H, dn), f(S, H, dr), f(S, R), f(S, dr)
    wkv_b = {"weight": f(R, H * (dn + dv)) * 0.2}
    w_uk, w_uv = mla_absorbed_weights(wkv_b, cfg, jnp.float32)
    pos = jnp.arange(S)
    seg = jnp.ones((S,), jnp.int32)
    with jax.default_matmul_precision("highest"):
        q_lat = jnp.concatenate(
            [jnp.einsum("shd,rhd->shr", q_nope, w_uk), q_pe], -1)
        o_lat = mla_attention_reference(
            q_lat, c, k_pe, q_positions=pos, kv_positions=pos,
            q_segment_ids=seg, kv_segment_ids=seg, scale=0.3)
        got = jnp.einsum("shr,rhd->shd", o_lat, w_uv)
        kv = (c @ wkv_b["weight"]).reshape(S, H, dn + dv)
        s = (jnp.einsum("qhd,khd->hqk", q_nope, kv[..., :dn])
             + jnp.einsum("qhd,kd->hqk", q_pe, k_pe)) * 0.3
        s = jnp.where((pos[:, None] >= pos[None])[None], s, -jnp.inf)
        want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                          kv[..., dn:])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


ROWS = {  # q_len, hist, t0, T, max_q_len[, pages a row's table holds]
    "decode": ([1, 1, 0, 1], [5, 33, 0, 160], [0, 1, 2, 3], 4, 1),
    "verify": ([3, 3, 0, 3], [5, 33, 0, 100], [0, 3, 6, 9], 12, 3),
    "chunk": ([40], [130], [0], 48, None),
    "cold": ([20, 9, 150], [0, 0, 0], [0, 20, 29], 192, None),
    "mixed": ([37, 1, 1, 1], [70, 9, 0, 191], [0, 37, 38, 39], 64, None),
    # chunks of 16 pages, awaited by count: the first row fills both buffer
    # slots (33 pages: 16 + 16 + 1), the rows behind it fetch 6 (bits 4 + 2)
    # and 11 (8 + 2 + 1) pages of their last chunk, one page, and none, so
    # what they leave unfetched holds the rows before
    "decode_by_count": ([1, 1, 1, 1, 1], [520, 350, 171, 3, 0],
                        [0, 1, 2, 3, 4], 5, 1, 40),
    "chunk_by_count": ([19, 1], [600, 271], [0, 19], 24, None, 40),
}


def _latent_kernel_case(kind):
    """``(args, t0, q_len, T, max_q_len)`` of one ROWS case: float32, a pool
    of ONE array ``[L, N, P, R + 128]`` (latent | rope key | zeros)."""
    q_len, hist, t0, T, mq, *rest = ROWS[kind]
    maxP = rest[0] if rest else 12
    rng = np.random.default_rng(5)
    L, N, P, R, dr, H = 2, 40, 16, 128, 64, 16
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    kv_pages = jnp.pad(f(L, N, P, R + dr), ((0, 0),) * 3 + ((0, 128 - dr),))
    tables = jnp.asarray(rng.integers(1, N, (len(q_len), maxP)), jnp.int32)
    args = (f(T, H, R + dr) * 0.3, f(T, R), f(T, dr), kv_pages,
            jnp.int32(1), jnp.asarray(t0, jnp.int32),
            jnp.asarray(q_len, jnp.int32), jnp.asarray(hist, jnp.int32),
            tables)
    return args, t0, q_len, T, mq


def _rows_of(x, t0, q_len, T):
    in_row = np.zeros(T, bool)
    for s, n in zip(t0, q_len):
        in_row[s:s + n] = True
    return np.asarray(x)[in_row]


@pytest.mark.parametrize("kind", list(ROWS))
def test_pallas_kernel_in_interpret_mode_against_the_reference(kind):
    from helix_tpu.ops.mla_kernel import mla_ragged_paged_attention_tpu

    args, t0, q_len, T, mq = _latent_kernel_case(kind)
    want = mla_ragged_paged_attention_reference(*args, scale=0.7)
    got = mla_ragged_paged_attention_tpu(
        *args, scale=0.7, max_q_len=mq, interpret=True)
    # float32 both sides; the kernel's online softmax against one softmax
    err = np.abs(_rows_of(got, t0, q_len, T)
                 - _rows_of(want, t0, q_len, T)).max()
    assert err < 1e-5, err


def test_pages_a_row_never_fetched_hold_nan_and_change_nothing():
    """The interpreter hands a kernel its scratch as NaN.  A row whose last
    chunk is partly fetched (5 tokens: one page of twelve) computes over a
    buffer whose other pages are still NaN: their weight is exactly 0 and
    they are zeroed ahead of the PV product, so the output is finite and
    the reference's.  With every page of the pool NaN but the row's own,
    nothing a row does not own is read into a live product either."""
    from helix_tpu.ops.mla_kernel import mla_ragged_paged_attention_tpu

    args, t0, q_len, T, mq = _latent_kernel_case("decode")
    q, c_new, r_new, kv_pages, layer, t0a, qla, hist, tables = args
    own = np.zeros(kv_pages.shape[1], bool)
    for row, h in enumerate(np.asarray(hist)):
        own[np.asarray(tables)[row, :-(-int(h) // 16)]] = True
    poisoned = jnp.where(own[None, :, None, None], kv_pages, jnp.nan)
    want = mla_ragged_paged_attention_reference(*args, scale=0.7)
    got = mla_ragged_paged_attention_tpu(
        q, c_new, r_new, poisoned, layer, t0a, qla, hist, tables,
        scale=0.7, max_q_len=mq, interpret=True)
    got = _rows_of(got, t0, q_len, T)
    assert np.isfinite(got).all()
    assert np.abs(got - _rows_of(want, t0, q_len, T)).max() < 1e-5


def test_kernel_geometry_it_cannot_lower_is_refused_by_name():
    from helix_tpu.ops.mla_kernel import check_mla_geometry
    from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry

    check_mla_geometry(16, 512, 64, 2)
    for bad in ((16, 500, 64, 2), (16, 512, 192, 2), (12, 512, 64, 2)):
        with pytest.raises(UnsupportedKernelGeometry):
            check_mla_geometry(*bad)


# ---- rope -----------------------------------------------------------------

def test_yarn_frequencies_and_scale_against_the_closed_form():
    c = DEEPSEEK_V2_LITE
    got = rope_ops.rope_frequencies(64, c.rope_theta, c.rope_scaling)
    want = reference.yarn_inv_freq(64, 10000.0, dict(c.rope_scaling))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # the fastest pair turns as published, the slowest is interpolated by
    # the factor, and the ramp between is monotone
    assert got[0] == pytest.approx(1.0)
    assert got[-1] == pytest.approx(10000.0 ** (-62 / 64) / 40, rel=1e-6)
    assert np.all(np.diff(got) < 0)
    rot, soft = rope_ops.yarn_attention_scales(c.rope_scaling)
    m = 0.1 * 0.707 * np.log(40) + 1
    assert rot == pytest.approx(1.0) and soft == pytest.approx(m * m)
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert mla_softmax_scale(c) == pytest.approx(192 ** -0.5 * m * m)
    assert rope_ops.yarn_attention_scales(None) == (1.0, 1.0)


def test_interleaved_pairs_rotate_as_the_reference_rotates_them():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((6, 3, 8)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((6, 8)), jnp.float32)
    pos = jnp.arange(6) + 3
    inv = jnp.asarray(rope_ops.rope_frequencies(8, 10000.0, None))
    a = rope_ops.apply_rope_interleaved(x, pos, inv)
    b = rope_ops.apply_rope_interleaved(y, pos, inv)
    ra = reference.rope_pairs(x, pos, inv, 1.0)
    rb = reference.rope_pairs(y, pos, inv, 1.0)
    # the program keeps the pairs de-interleaved: a permutation both sides
    # of a score share
    np.testing.assert_allclose(
        jnp.concatenate([ra[..., 0::2], ra[..., 1::2]], -1), a, atol=1e-6)
    np.testing.assert_allclose(
        jnp.einsum("shd,sd->sh", a, b), jnp.einsum("shd,sd->sh", ra, rb),
        atol=1e-5)


# ---- the expert layer -----------------------------------------------------

def _experts(cfg, key, int8=False):
    p = init_params(cfg, key, int8=int8)["layers"]
    pick = lambda t: jax.tree.map(lambda a: a[0], t)  # noqa: E731
    router = pick(p["router"])
    w = router["weight"].astype(jnp.float32)
    if "scale" in router:
        w = w * router["scale"]
    return w, pick(p["experts"])


def _loop_over_experts(x, router_w, experts, cfg):
    T = x.shape[0]
    w, idx = route(x, router_w, cfg)
    out = jnp.zeros_like(x)
    for e in range(cfg.num_experts):
        def W(n):
            leaf = experts[n]
            m = leaf["weight"][e].astype(jnp.float32)
            return m * leaf["scale"][e] if "scale" in leaf else m
        y = (jax.nn.silu(x @ W("w_gate")) * (x @ W("w_up"))) @ W("w_down")
        out = out + jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None] * y
    assert w.shape == (T, cfg.num_experts_per_tok)
    return out


@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("shape", [(1, 37), (5, 1), (3, 8)],
                         ids=["prefill", "decode", "verify"])
def test_grouped_experts_equal_a_loop_over_experts(shape, int8):
    cfg = tiny()
    router_w, experts = _experts(cfg, jax.random.PRNGKey(7), int8)
    x = jax.random.normal(jax.random.PRNGKey(8), shape + (64,))
    with jax.default_matmul_precision("highest"):
        got, dropped = moe_ffn(x, router_w, experts, cfg, jax.nn.silu,
                               return_dropped=True)
        want = _loop_over_experts(x.reshape(-1, 64), router_w, experts, cfg)
    assert int(dropped) == 0
    assert np.abs(np.asarray(got).reshape(-1, 64)
                  - np.asarray(want)).max() < 1e-5


def test_dropless_when_every_token_picks_the_same_experts():
    cfg = tiny()
    router_w, experts = _experts(cfg, jax.random.PRNGKey(9))
    # one token repeated: all 64 rows route to the same three experts,
    # 21 times the capacity a factor of 1.5 would give each of them
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(10), (1, 1, 64)),
                 (1, 64, 1))
    with jax.default_matmul_precision("highest"):
        got, stats = moe_ffn(x, router_w, experts, cfg, jax.nn.silu,
                             return_stats=True)
        want = _loop_over_experts(x[0], router_w, experts, cfg)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-5
    dropped, routed, ratio, touched, fill, away = np.asarray(stats)
    assert away == 0                          # every expert is here
    assert (dropped, routed, touched) == (0, 64 * 3, 3)
    assert fill == 0.5      # three groups of 64 rows: three visits of 128
    assert ratio == pytest.approx(8 / 3)      # busiest over the mean load


def test_masked_tokens_are_never_routed():
    cfg = tiny()
    router_w, experts = _experts(cfg, jax.random.PRNGKey(11))
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 12, 64))
    mask = jnp.asarray([[True] * 7 + [False] * 5])
    garbage = x.at[0, 7:].set(jnp.nan)
    with jax.default_matmul_precision("highest"):
        want = moe_ffn(x[:, :7], router_w, experts, cfg, jax.nn.silu)
        got, stats = moe_ffn(garbage, router_w, experts, cfg, jax.nn.silu,
                             token_mask=mask, return_stats=True)
    np.testing.assert_allclose(got[0, :7], want[0], atol=1e-6)
    assert np.asarray(stats)[1] == 7 * 3


def test_mixtral_router_is_unchanged_and_both_dispatches_agree():
    mix = ModelConfig.tiny(num_experts=4, dtype="float32")
    assert mix.moe_renormalize and mix.expert_capacity_factor == 1.5
    x = jax.random.normal(jax.random.PRNGKey(13), (9, 64))
    w_r = jax.random.normal(jax.random.PRNGKey(14), (64, 4))
    w, idx = route(x, w_r, mix)
    vals, want_idx = jax.lax.top_k(x @ w_r, 2)         # top-2, then softmax
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(w, jax.nn.softmax(vals, -1), atol=1e-6)
    experts = jax.tree.map(
        lambda a: a[0],
        init_params(mix, jax.random.PRNGKey(15))["layers"]["experts"])
    roomy = dataclasses.replace(mix, expert_capacity_factor=4.0)
    dropless = dataclasses.replace(mix, expert_capacity_factor=0.0)
    with jax.default_matmul_precision("highest"):
        a = moe_ffn(x[None], w_r, experts, roomy, jax.nn.silu)
        b = moe_ffn(x[None], w_r, experts, dropless, jax.nn.silu)
    np.testing.assert_allclose(a, b, atol=1e-5)


# ---- configuration, parameters, cache --------------------------------------

def test_catalog_entry_is_the_published_config():
    c = CATALOG["deepseek-ai/DeepSeek-V2-Lite"]
    assert (c.hidden_size, c.num_heads, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim) == (2048, 16, 512, 128, 64, 128)
    assert (c.num_experts, c.expert_width, c.num_experts_per_tok,
            c.num_shared_experts, c.first_k_dense, c.intermediate_size,
            c.vocab_size, c.num_layers) == (64, 1408, 6, 2, 1, 10944,
                                            102400, 27)
    assert not c.moe_renormalize and c.expert_capacity_factor == 0
    assert c.num_moe_layers == 26 and c.is_mla and c.q_lora_rank == 0


HF_V2 = {
    "model_type": "deepseek_v2", "vocab_size": 300, "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "n_shared_experts": 2, "num_experts_per_tok": 3,
    "first_k_dense_replace": 1, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "rope_scaling": dict(YARN),
}


def test_a_compressed_query_loads_and_is_the_unabsorbed_form():
    """``q_lora_rank`` in a DeepSeek-form config: ``q = n(x W_qa) W_qb``.  The
    layer's absorbed attention against explicit per-head K and V built from
    the latent, one layer, float32: another order of the same sums, 1e-5."""
    from helix_tpu.models import llama
    from helix_tpu.ops.norms import rms_norm

    cfg = dataclasses.replace(ModelConfig.from_hf_config(
        dict(HF_V2, q_lora_rank=24), name="tiny-q-lora"), dtype="float32")
    assert cfg.q_lora_rank == 24 and cfg.moe_scoring == "softmax"
    params = init_params(cfg, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    assert lp["wq_a"]["weight"].shape == (64, 24) and "wq" not in lp
    assert lp["wq_b"]["weight"].shape == (24, 4 * 24)
    S, H, R, dn, dr, dv = 20, 4, 32, 16, 8, 16
    h = jax.random.normal(jax.random.PRNGKey(1), (1, S, 64))
    pos = jnp.arange(S)[None]
    inv = jnp.asarray(rope_ops.rope_frequencies(
        dr, cfg.rope_theta, cfg.rope_scaling))
    with jax.default_matmul_precision("highest"):
        got, _, _ = llama._mla_attention(
            h, lp, None, cfg, pos, inv, prefill_attn_fn)
        x = rms_norm(h[0], lp["attn_norm"]["weight"], 1e-6)
        c_q = rms_norm(x @ lp["wq_a"]["weight"], lp["q_a_norm"]["weight"],
                       1e-6)
        q = (c_q @ lp["wq_b"]["weight"]).reshape(S, H, dn + dr)
        ck = x @ lp["wkv_a"]["weight"]
        c = rms_norm(ck[:, :R], lp["kv_norm"]["weight"], 1e-6)
        kv = (c @ lp["wkv_b"]["weight"]).reshape(S, H, dn + dv)
        inv_ref = jnp.asarray(reference.yarn_inv_freq(dr, 10000.0, YARN))
        q_pe = reference.rope_pairs(q[..., dn:], pos[0], inv_ref, 1.0)
        k_pe = reference.rope_pairs(ck[:, R:], pos[0], inv_ref, 1.0)
        s = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kv[..., :dn])
             + jnp.einsum("qhd,kd->hqk", q_pe, k_pe)) * mla_softmax_scale(cfg)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kv[..., dn:])
        want = h[0] + a.reshape(S, H * dv) @ lp["wo"]["weight"]
    assert float(jnp.abs(got[0] - want).max()) < 1e-5
    assert float(jnp.abs(want - h[0]).max()) > 1e-3


@pytest.mark.parametrize("bad,match", [
    (dict(topk_method="group_limited_greedy", n_group=8), "grouped top-k"),
    (dict(n_group=4, topk_group=2), "grouped top-k"),
    (dict(topk_method="noaux_tc"), "grouped top-k"),
    (dict(moe_layer_freq=2), "every layer after"),
    (dict(scoring_func="tanh"), "scoring_func")])
def test_routers_that_are_not_served_are_still_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(dict(HF_V2, **bad))


def test_sigmoid_scores_in_a_deepseek_form_config_load():
    cfg = ModelConfig.from_hf_config(dict(HF_V2, scoring_func="sigmoid"))
    assert cfg.moe_scoring == "sigmoid" and cfg.moe_expert_bias


@pytest.mark.parametrize("seed", [0, 1])
def test_seeded_embedding_keeps_tokens_apart_for_the_router(seed):
    """A dropless expert model's seeded embedding has unit-RMS rows: the
    router then spreads random tokens over the experts.  At the dense
    families' 0.02 (unchanged) the hidden states are one common vector and
    the busiest expert takes about twice its share, even at this size."""
    cfg = tiny(hidden_size=128, num_layers=5, num_experts=16,
               vocab_size=2000, intermediate_size=256,
               moe_intermediate_size=64)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    dense = init_params(tiny(num_experts=0, kv_lora_rank=0, head_dim=16),
                        jax.random.PRNGKey(seed))
    assert abs(float(params["embed"]["weight"].std()) - 1.0) < 0.02
    assert abs(float(dense["embed"]["weight"].std()) - 0.02) < 0.002
    toks = jnp.asarray([tokens_of(256, seed, 2000)], jnp.int32)

    def busiest_over_mean(embed_scale):
        p = dict(params, embed={
            "weight": params["embed"]["weight"] * embed_scale})
        stats = forward(p, cfg, toks, jnp.arange(256)[None],
                        attn_fn=prefill_attn_fn, return_moe_stats=True)[2]
        assert float(stats["vector"][3]) == 16      # every expert touched
        return float(stats["vector"][2])

    assert busiest_over_mean(1.0) < 1.7
    assert busiest_over_mean(0.02) > 1.9


def test_int8_tree_and_logical_axes_cover_every_new_tensor():
    from helix_tpu.ops.quant import quantize_params, quantized_logical_axes

    cfg = tiny()
    # (shapes, dtypes and structure are all that is asked: nothing is run)
    born = jax.eval_shape(
        lambda k: init_params(cfg, k, int8=True), jax.random.PRNGKey(0))
    made = jax.eval_shape(
        lambda k: quantize_params(init_params(cfg, k)), jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)  # noqa
    assert shapes(born) == shapes(made)
    assert set(born) == {"embed", "layers", "dense_layers", "final_norm",
                         "lm_head"}
    assert born["layers"]["wkv_b"]["weight"].dtype == jnp.int8
    assert born["layers"]["shared"]["w_up"]["weight"].shape == (2, 64, 64)
    assert born["dense_layers"]["w_gate"]["weight"].shape == (1, 64, 96)
    axes = quantized_logical_axes(param_logical_axes(cfg))
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=is_axes)
    ) == jax.tree.structure(jax.tree.map(lambda a: 0, born))
    jax.tree.map(lambda ax, leaf: None if len(ax) == leaf.ndim else 1 / 0,
                 axes, born, is_leaf=is_axes)


def test_latent_pool_counts_what_it_allocates_and_moves_by_page():
    cfg = tiny()
    cc = CacheConfig(num_pages=10, page_size=8, dtype="float32")
    # ONE array: a token's latent, then its rope key padded to 128 lanes
    assert latent_widths(cfg) == (32, 128)
    assert cc.page_shapes(cfg) == ((3, 8, 32 + 128),)
    assert cc.page_bytes(cfg) == 3 * 8 * (32 + 128) * 4
    full = CacheConfig(num_pages=1, page_size=16, dtype="bfloat16")
    # 512 + 64 values a token a layer, the rope key padded to 128 lanes:
    # the bytes the two-array pool had (1,280 a token and layer)
    assert full.page_shapes(DEEPSEEK_V2_LITE) == ((27, 16, 640),)
    assert full.page_bytes(DEEPSEEK_V2_LITE) == 27 * 16 * 640 * 2
    assert CacheConfig.fit_hbm(cfg, 10 * cc.page_bytes(cfg) + 5, page_size=8,
                               dtype="float32").num_pages == 10
    cache = PagedKVCache.create(cfg, cc)
    assert cache.latent and cache.k_pages.shape == (3, 10, 8, 160)
    assert cache.v_pages is None and cache.carry()[1] is None
    assert jax.tree.leaves(cache) == [cache.k_pages]
    c = jnp.arange(3 * 1 * 4 * 32, dtype=jnp.float32).reshape(3, 1, 4, 32)
    r = jnp.ones((3, 1, 4, 8), jnp.float32)
    pages = jnp.asarray([[2, 2, 5, 0]])
    offs = jnp.asarray([[6, 7, 0, 0]])
    cache = write_kv(cache, c, r, pages, offs,
                     jnp.asarray([[True, True, True, False]]))
    assert cache.v_pages is None
    np.testing.assert_array_equal(cache.k_pages[:, 2, 6, :32], c[:, 0, 0])
    np.testing.assert_array_equal(cache.k_pages[:, 5, 0, :32], c[:, 0, 2])
    assert float(cache.k_pages[0, 2, 7, 32:40].sum()) == 8   # the rope key
    assert float(cache.k_pages[..., 40:].sum()) == 0      # the lane padding
    held = gather_pages(cache, [2, 5])
    assert held[0]["k"].shape == (3, 8, 160) and held[0]["v"] is None
    moved = restore_pages(PagedKVCache.create(cfg, cc), [7, 3], held)
    assert moved.v_pages is None
    np.testing.assert_array_equal(moved.k_pages[:, 7], cache.k_pages[:, 2])
    np.testing.assert_array_equal(moved.k_pages[:, 3], cache.k_pages[:, 5])


def test_write_latent_is_a_plain_loop_over_layers_and_tokens():
    """``write_kv`` on a latent pool against the loop it stands for: each
    valid token's row of each layer is ``[c | r | zeros]`` at its (page,
    offset), rows of padding tokens land on the garbage page 0 alone, and
    every other row of the pool keeps what it held."""
    cfg = tiny()
    cc = CacheConfig(num_pages=12, page_size=8, dtype="float32")
    rng = np.random.default_rng(3)
    L, B, S, R, dr = 3, 2, 5, 32, 8
    before = jnp.asarray(
        rng.standard_normal((L, 12, 8, R + 128)), jnp.float32)
    cache = PagedKVCache(k_pages=before, v_pages=None)
    c = jnp.asarray(rng.standard_normal((L, B, S, R)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((L, B, S, dr)), jnp.float32)
    pages = rng.integers(1, 12, (B, S))
    offs = np.stack([rng.permutation(8)[:S] for _ in range(B)])
    pages[1] = pages[0] + 1 - 11 * (pages[0] == 11)  # no (page, offset) twice
    valid = np.asarray([[True] * 5, [True, True, True, False, False]])
    got = write_kv(cache, c, r, jnp.asarray(pages), jnp.asarray(offs),
                   jnp.asarray(valid))
    want = np.array(before)
    for lyr in range(L):
        for b in range(B):
            for t in range(S):
                if valid[b, t]:
                    row = np.zeros(R + 128, np.float32)
                    row[:R], row[R:R + dr] = c[lyr, b, t], r[lyr, b, t]
                    want[lyr, pages[b, t], offs[b, t]] = row
    np.testing.assert_array_equal(
        np.asarray(got.k_pages)[:, 1:], want[:, 1:])
    # the garbage page took the padding tokens' rows at offset 0 and
    # nothing else
    np.testing.assert_array_equal(
        np.asarray(got.k_pages)[:, 0, 1:], want[:, 0, 1:])
    assert got.v_pages is None and got.k_pages.shape == before.shape


def _mid_decode(eng, rid, prompt, cut):
    req = Request(id=rid, prompt_tokens=list(prompt),
                  sampling=SamplingParams(max_tokens=12, temperature=0.0))
    eng.add_request(req)
    while len(req.output_tokens) < cut and eng.has_work():
        eng.step()
    return req


def test_a_request_moves_between_latent_pools_by_snapshot():
    """Export mid-generation, through the wire format, import into a second
    engine, continue: the uninterrupted run's tokens.  A page travels as
    its one array (``"v"`` None) under the digest the exporter stamped."""
    from helix_tpu.serving import migration

    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(5))
    prompt = tokens_of(21, seed=8)
    ref = _engine(cfg, params).generate(
        [prompt], SamplingParams(max_tokens=12, temperature=0.0))[0]
    a, b = _engine(cfg, params), _engine(cfg, params)
    req_a = _mid_decode(a, "m", prompt, 5)
    snap = a.export_request("m")
    assert snap is not None and snap.has_kv
    assert (snap.kv_heads, snap.head_dim) == (0, 32 + 128)
    assert all(p["v"] is None and p["k"].shape == (3, 8, 160)
               for p in snap.pages)
    head = list(req_a.output_tokens[:5])
    a.abort("m")
    snap = migration.wire_to_snapshot(migration.snapshot_to_wire(snap))
    req_b = b.import_request(snap)
    while not req_b.finished:
        b.step()
    assert head + list(req_b.output_tokens[5:]) == list(ref)


@pytest.mark.parametrize("lie,field,code", [
    # the two-array layout this pool had: its snapshots stated the latent
    # width alone
    (dict(head_dim=32), "head_dim", "snapshot_incompatible"),
    (dict(kv_heads=4, head_dim=24), "kv_heads", "snapshot_incompatible"),
], ids=["the_old_two_array_layout", "a_kv_pool"])
def test_a_snapshot_of_another_pool_geometry_is_refused_by_name(
        lie, field, code):
    from helix_tpu.engine.engine import SnapshotError

    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(5))
    a, b = _engine(cfg, params), _engine(cfg, params)
    _mid_decode(a, "g", tokens_of(21, seed=8), 3)
    snap = dataclasses.replace(a.export_request("g"), **lie)
    free = b.allocator.free_pages
    with pytest.raises(SnapshotError, match=field) as ei:
        b.import_request(snap)
    assert ei.value.code == code
    assert b.allocator.free_pages == free       # refused before any page


def test_a_latent_page_with_a_second_array_is_refused():
    from helix_tpu.engine.engine import SnapshotError

    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(5))
    a, b = _engine(cfg, params), _engine(cfg, params)
    _mid_decode(a, "h", tokens_of(21, seed=8), 3)
    snap = a.export_request("h")
    snap.pages[0] = dict(snap.pages[0], v=np.zeros((3, 8, 128), np.float32))
    with pytest.raises(SnapshotError, match="k/v buffers"):
        b.import_request(snap)


def test_latent_pages_spill_to_the_host_tier_and_come_back():
    """The host pool keeps a latent page as its one array: put, checksum,
    restore into other pages of another pool."""
    from helix_tpu.engine.kv_cache import HostPagePool, page_checksum

    cfg = tiny()
    cc = CacheConfig(num_pages=10, page_size=8, dtype="float32")
    cache = PagedKVCache.create(cfg, cc)
    cache = dataclasses.replace(cache, k_pages=jax.random.normal(
        jax.random.PRNGKey(0), cache.k_pages.shape))
    pool = HostPagePool(budget_bytes=1 << 20)
    held = gather_pages(cache, [4, 9])
    for i, page in enumerate(held):
        assert pool.put(("seq", "r", i), page, pinned=True)
    pool.drain_pending()
    assert pool.used_bytes == 2 * cc.page_bytes(cfg)
    back = [pool.take_restored(("seq", "r", i)) for i in range(2)]
    assert back[0]["v"] is None
    assert page_checksum(back[1]) == page_checksum(
        {k: None if v is None else np.asarray(v)
         for k, v in held[1].items()})
    moved = restore_pages(PagedKVCache.create(cfg, cc), [1, 2], back)
    np.testing.assert_array_equal(moved.k_pages[:, 2], cache.k_pages[:, 9])


REFUSED = {
    "int8 kv": dict(kv_cache_dtype="int8"),
    "adapters": dict(adapter_pool_slots=2),
    "speculation": dict(enable_spec_decode=True),
    "tiered residency": dict(ctx_hot_pages=4, host_pool_bytes=1 << 20),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_what_latent_attention_is_not_served_with_is_refused(what):
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(UnsupportedForModel, match="latent attention"):
        _engine(cfg, params, **REFUSED[what])


def test_prefix_cache_works_on_the_latent_pool():
    """The same prompt twice: the second is served from the pages the
    first left in the prefix cache (history through the latent pool), and
    returns what the cold run returned."""
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(5))
    eng = _engine(cfg, params, enable_prefix_cache=True)
    prompt = tokens_of(30, seed=4)
    sp = SamplingParams(max_tokens=5, temperature=0.0)
    first = eng.generate([prompt], sp)[0]
    hits = eng.prefix_cache.stats["hits"]
    second = eng.generate([prompt], sp)[0]
    assert eng.prefix_cache.stats["hits"] > hits
    assert first == second


def test_loader_reads_deepseek_v2_tensor_names(tmp_path):
    """A checkpoint under the published tensor names, made from a seeded
    tree: loaded back it is that tree, and its config.json gives the
    config."""
    import json

    from safetensors.numpy import save_file

    from helix_tpu.models.loader import load_params

    cfg = tiny()
    params = jax.tree.map(np.asarray, init_params(cfg, jax.random.PRNGKey(6)))
    t = {"model.embed_tokens.weight": params["embed"]["weight"],
         "model.norm.weight": params["final_norm"]["weight"],
         "lm_head.weight": params["lm_head"]["weight"].T}
    names = {"wq": "self_attn.q_proj", "wkv_a": "self_attn.kv_a_proj_with_mqa",
             "wkv_b": "self_attn.kv_b_proj", "wo": "self_attn.o_proj"}
    mlp = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
    for i in range(cfg.num_layers):
        st, j = ((params["dense_layers"], i) if i < cfg.first_k_dense
                 else (params["layers"], i - cfg.first_k_dense))
        p = f"model.layers.{i}."
        t[p + "input_layernorm.weight"] = st["attn_norm"]["weight"][j]
        t[p + "post_attention_layernorm.weight"] = st["mlp_norm"]["weight"][j]
        t[p + "self_attn.kv_a_layernorm.weight"] = st["kv_norm"]["weight"][j]
        for ours, theirs in names.items():
            t[p + theirs + ".weight"] = st[ours]["weight"][j].T
        if "router" not in st:
            for ours, theirs in mlp.items():
                t[p + f"mlp.{theirs}.weight"] = st[ours]["weight"][j].T
            continue
        t[p + "mlp.gate.weight"] = st["router"]["weight"][j].T
        for ours, theirs in mlp.items():
            t[p + f"mlp.shared_experts.{theirs}.weight"] = (
                st["shared"][ours]["weight"][j].T)
            for e in range(cfg.num_experts):
                t[p + f"mlp.experts.{e}.{theirs}.weight"] = (
                    st["experts"][ours]["weight"][j, e].T)
    save_file({k: np.ascontiguousarray(v) for k, v in t.items()},
              str(tmp_path / "model.safetensors"))
    hf = dict(hf_of(cfg), model_type="deepseek_v2", vocab_size=300,
              hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
              n_shared_experts=2, q_lora_rank=None,
              max_position_embeddings=512, torch_dtype="float32")
    (tmp_path / "config.json").write_text(json.dumps(hf))
    got_cfg, got = load_params(str(tmp_path), dtype="float32")
    assert dataclasses.replace(got_cfg, name=cfg.name) == cfg
    jax.tree.map(np.testing.assert_array_equal, got, params)


def test_page_fetches_are_counted_from_the_hosts_mirrors():
    """``helix_mla_page_fetches_total``: a launch's history pages (live rows'
    ``ceil(hist / page)`` x query blocks x latent layers) on the launch's
    span and, summed, on ``/metrics``; by hand for a 40-token prompt in
    chunks of 16 at page 8 and three decode steps."""
    from helix_tpu.obs import trace as obs_trace
    from helix_tpu.serving.engine_loop import EngineLoop
    from helix_tpu.serving.openai_api import OpenAIServer
    from helix_tpu.serving.registry import ModelRegistry, ServedModel
    from helix_tpu.serving.tokenizer import ByteTokenizer

    cfg = tiny()
    eng = _engine(cfg, init_params(cfg, jax.random.PRNGKey(3)),
                  decode_steps_per_sync=1)
    seen = []
    orig = obs_trace.phase

    def phase(name, *a, **kw):
        if name == "helix.loop.launch":
            seen.append(kw["mla_page_fetches"])
        return orig(name, *a, **kw)

    obs_trace.phase = phase
    try:
        req = Request(id="p", prompt_tokens=tokens_of(40, seed=1),
                      sampling=SamplingParams(max_tokens=4, temperature=0.0))
        eng.add_request(req)
        while eng.has_work():
            eng.step()
    finally:
        obs_trace.phase = orig
    L = cfg.num_attn_layers
    # chunks at 0, 16, 32 tokens of history: 0, 2 and 4 pages under two
    # 8-token blocks each, then one-token rows over 40, 41, 42 tokens
    by_hand = [0, 2 * 2 * L, 4 * 1 * L, 5 * L, 6 * L, 6 * L]
    assert seen == by_hand, seen
    assert eng.mixer_counts["mla_page_fetches"] == sum(by_hand)
    # the latent kernel's block stays 8 tokens whatever the bucket (the dense
    # kernel's long block is not its own), and the dense kernel ran nothing
    assert [cfg.page_kind.query_block(cfg, rung, 1)
            for rung in (8, 16, 512)] == [8] * 3
    assert eng.chunk_q_block == 8 and (
        "attn_query_blocks" not in eng.mixer_counts)
    registry = ModelRegistry()
    registry.register(ServedModel(
        name="tiny-mla", loop=EngineLoop(eng, "tiny-mla"),
        tokenizer=ByteTokenizer(), context_length=128))
    text = OpenAIServer(registry).obs.render()
    line = next(ln for ln in text.splitlines()
                if ln.startswith("helix_mla_page_fetches_total{"))
    assert float(line.rsplit(" ", 1)[1]) == sum(by_hand)
    assert "helix_attn_query_blocks_total" not in text
