"""LFM2-style hybrid decoders on the CPU at a small size, float32, seeded
weights: gated short convolutions with a per-slot state pool beside the page
pool, GQA layers whose heads pack two to a lane tile, the sigmoid-and-bias
router, a layer stack of runs of three kinds.  The oracle is the benchmark's
plain reference (``benchmark/lib/reference_hybrid_conv_moe_decoder.py``);
everything is compared by LOGITS."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import (  # noqa: E402
    reference_hybrid_conv_moe_decoder as reference,
)
from helix_tpu.engine.engine import (  # noqa: E402
    Engine, EngineConfig, Request, SamplingParams, UnsupportedForModel,
)
from helix_tpu.engine.kv_cache import (  # noqa: E402
    CacheConfig, PagedKVCache, PrefixCache,
)
from helix_tpu.models.common import (  # noqa: E402
    CATALOG, LFM2_8B_A1B, ModelConfig,
)
from helix_tpu.models.llama import (  # noqa: E402
    forward, init_params, param_logical_axes, prefill_attn_fn,
)
from helix_tpu.models.moe import route  # noqa: E402
from helix_tpu.ops.paged import (  # noqa: E402
    pack_heads, ragged_paged_attention, ragged_paged_attention_reference,
    unpack_heads,
)

# two dense layers, then a pattern that holds all three kinds of layer in
# uneven runs, one period of which repeats: conv+dense x2 | (attn+moe |
# conv+moe x2) twice | attn+moe | conv+moe x3
_A, _C = "full_attention", "conv"
HF = dict(
    model_type="lfm2_moe", vocab_size=256, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=64,
    intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=12,
    num_dense_layers=2,
    layer_types=[_C, _C, _A, _C, _C, _A, _C, _C, _A, _C, _C, _C],
    conv_L_cache=3, conv_bias=False, norm_eps=1e-5, rope_theta=1e6,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    use_expert_bias=True, routed_scaling_factor=1,
    max_position_embeddings=512,
)
# float32, the same mathematics through another order of operations (the
# engine's pages, state pool, chunks and grouped product against whole-
# sequence sums): measured 1e-5 and under on logits of spread 9
TOL = 1e-4


def tiny(**kw):
    cfg = ModelConfig.from_hf_config(dict(HF, **kw), name="tiny-hybrid")
    return dataclasses.replace(cfg, dtype="float32")


def tokens_of(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, size=n).tolist()


def _engine(cfg, params, **kw):
    ecfg = EngineConfig(
        max_decode_batch=kw.pop("slots", 2), page_size=8, num_pages=96,
        max_pages_per_seq=16, max_prefill_len=16, attn_backend="reference",
        **kw)
    return Engine(cfg, params, ecfg)


def _run(eng, reqs, watch):
    """Step ``eng`` over ``reqs``; the watched request's next-token logits,
    ``{tokens it had put out: logits [V]}``, read after every step that
    gave it one (a step may give it two: the first and a decoded one)."""
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


def _worst(a, b):
    """The largest difference over the steps both runs read."""
    both = sorted(set(a) & set(b))
    assert len(both) >= 3, (sorted(a), sorted(b))
    return max(np.abs(a[n] - b[n]).max() for n in both)


def _req(rid, prompt, n=6):
    return Request(id=rid, prompt_tokens=prompt,
                   sampling=SamplingParams(max_tokens=n, temperature=0.0))


# ---- the model --------------------------------------------------------------


def test_config_reads_the_published_keys_and_runs_of_kinds():
    cfg = tiny()
    assert cfg.mixers == tuple(
        "attn" if t == _A else "conv" for t in HF["layer_types"])
    assert (cfg.num_attn_layers, cfg.num_conv_layers) == (3, 9)
    assert cfg.moe_scoring == "sigmoid" and cfg.moe_expert_bias
    assert cfg.qk_norm and cfg.tie_word_embeddings and cfg.conv_kernel == 3
    groups = [(g.reps, [(r.key, r.mixer, r.moe, r.count, r.first, r.step)
                        for r in g.runs]) for g in cfg.layer_runs()]
    assert groups == [
        (1, [("run00", "conv", False, 2, 0, 2)]),
        # the period "attention, two convolutions" twice: one group
        (2, [("run01", "attn", True, 1, 0, 1),
             ("run02", "conv", True, 2, 2, 2)]),
        (1, [("run05", "attn", True, 1, 2, 1)]),
        (1, [("run06", "conv", True, 3, 6, 3)])]
    # the two-run case keeps the names it had, each a group of its own
    from helix_tpu.models.common import DEEPSEEK_V2_LITE, QWEN2_7B

    def plain(m):
        assert all(g.reps == 1 and len(g.runs) == 1 for g in m.layer_runs())
        return [(g.runs[0].key, g.runs[0].count) for g in m.layer_runs()]

    assert plain(DEEPSEEK_V2_LITE) == [("dense_layers", 1), ("layers", 26)]
    assert plain(QWEN2_7B) == [("layers", 28)]
    with pytest.raises(ValueError, match="conv_bias"):
        tiny(conv_bias=True)


def test_catalog_entry_is_the_published_config():
    m = CATALOG["LiquidAI/LFM2-8B-A1B"]
    assert m is LFM2_8B_A1B
    assert (m.num_layers, m.hidden_size, m.num_heads, m.num_kv_heads,
            m.head_dim) == (24, 2048, 32, 8, 64)
    assert (m.num_conv_layers, m.num_attn_layers) == (18, 6)
    # thirteen runs in three groups: five loop bodies a forward pass
    assert [(g.reps, [(r.key, r.count) for r in g.runs])
            for g in m.layer_runs()] == [
        (1, [("run00", 2)]), (4, [("run01", 1), ("run02", 3)]),
        (2, [("run09", 1), ("run10", 2)])]
    assert (m.num_experts, m.num_experts_per_tok, m.expert_width,
            m.intermediate_size, m.first_k_dense) == (32, 4, 1792, 7168, 2)
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        import json

        row = next(r for r in map(json.loads, open(path))
                   if r["name"] == "LFM2-8B-A1B")
        got = ModelConfig.from_hf_config(row["config"], name=m.name)
        assert got == m


@pytest.mark.parametrize("int8", [False, True])
def test_forward_agrees_with_the_reference(int8):
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(3), int8=int8)
    toks = jnp.asarray([tokens_of(40)], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, toks, jnp.arange(40)[None],
                         attn_fn=prefill_attn_fn)
    want = reference.forward(params, HF, toks[0])
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < TOL


def test_reference_in_blocks_of_layers_is_the_full_forward():
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(2))
    toks = jnp.asarray(tokens_of(12), jnp.int32)
    h = reference.forward(params, HF, toks, layers=(0, 4), head=False)
    got = reference.forward(params, HF, toks, layers=(4, 12), h=h)
    want = reference.forward(params, HF, toks)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_int8_tree_and_logical_axes_cover_every_new_tensor():
    from helix_tpu.ops.quant import quantize_params, quantized_logical_axes

    cfg = tiny()
    born = init_params(cfg, jax.random.PRNGKey(0), int8=True)
    made = quantize_params(init_params(cfg, jax.random.PRNGKey(0)))
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)  # noqa
    assert shapes(born) == shapes(made)
    assert set(born) == {"embed", "final_norm", "run00", "run01", "run02",
                         "run05", "run06"}
    assert born["run00"]["in_proj"]["weight"].dtype == jnp.int8
    assert born["run00"]["in_proj"]["weight"].shape == (2, 64, 192)
    # the taps and the bias are no matmul weights: they stay as drawn; a
    # run of a repeated group holds every repetition's layers
    assert born["run02"]["conv"]["taps"].shape == (4, 64, 3)
    assert born["run02"]["conv"]["taps"].dtype == jnp.float32
    assert born["run02"]["expert_bias"]["bias"].shape == (4, 8)
    assert "wq" not in born["run02"] and "in_proj" not in born["run01"]
    assert born["run01"]["q_norm"]["weight"].shape == (2, 64)
    assert reference.layer_homes(HF)[2:8] == [
        ("run01", 0), ("run02", 0), ("run02", 1),
        ("run01", 1), ("run02", 2), ("run02", 3)]
    axes = quantized_logical_axes(param_logical_axes(cfg))
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=is_axes)
    ) == jax.tree.structure(jax.tree.map(lambda a: 0, born))


@pytest.mark.parametrize("seed", [0, 1])
def test_seeded_draws_make_the_new_parts_visible(seed):
    """The draws ISSUE 32 asks of ``init_params``: the expert bias changes
    the top-k of a measurable share of tokens, and a conv layer's branch
    is of the size of an attention layer's (not thirty times smaller)."""
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(seed))
    stack = params["run02"]
    x = jax.random.normal(jax.random.PRNGKey(9), (256, 64))
    w_r = stack["router"]["weight"][0]
    _, with_bias = route(x, w_r, cfg, stack["expert_bias"]["bias"][0])
    _, without = route(x, w_r, cfg, None)
    moved = np.mean(np.any(np.sort(np.asarray(with_bias), -1)
                           != np.sort(np.asarray(without), -1), axis=-1))
    assert moved > 0.2, moved
    assert float(jnp.std(stack["conv"]["taps"])) > 0.3


# ---- the router -------------------------------------------------------------


def test_router_bias_moves_the_choice_and_never_the_weight():
    cfg = tiny()
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
    w_r = jax.random.normal(jax.random.PRNGKey(2), (64, 8)) * 0.2
    bias = jnp.zeros((8,)).at[5].set(10.0)        # expert 5 always chosen
    w, idx = route(x, w_r, cfg, bias)
    s = jax.nn.sigmoid(x @ w_r)
    assert bool(jnp.all(jnp.any(idx == 5, axis=-1)))
    picked = jnp.take_along_axis(s, idx, axis=-1)   # the UNBIASED scores
    want = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(w), np.asarray(want), rtol=1e-6)
    # the published 1e-6: the weights of a token sum to a little under 1
    total = np.asarray(w.sum(-1))
    assert np.all(total < 1.0) and np.all(total > 1.0 - 1e-5)
    # and the reference's router is the same function
    rw, ridx = reference.route(x, w_r, bias, HF)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), rtol=1e-6)
    # without renormalisation the scores are kept as they are
    raw, _ = route(x, w_r, dataclasses.replace(cfg, moe_renormalize=False),
                   bias)
    np.testing.assert_allclose(np.asarray(raw), np.asarray(picked),
                               rtol=1e-6)


def test_router_ties_go_to_the_lower_expert_on_both_sides():
    cfg = tiny()
    x = jnp.ones((3, 64))
    w_r = jnp.zeros((64, 8))                       # every score 0.5
    _, idx = route(x, w_r, cfg, jnp.zeros((8,)))
    _, ridx = reference.route(x, w_r, jnp.zeros((8,)), HF)
    np.testing.assert_array_equal(np.asarray(idx), [[0, 1]] * 3)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))


def test_softmax_routers_are_unchanged():
    cfg = dataclasses.replace(tiny(), moe_scoring="softmax")
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
    w_r = jax.random.normal(jax.random.PRNGKey(2), (64, 8))
    w, idx = route(x, w_r, cfg)
    top, tidx = jax.lax.top_k(x @ w_r, 2)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(tidx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(
        jax.nn.softmax(top, -1)), rtol=1e-6)


# ---- the two pools ----------------------------------------------------------


def test_pools_count_what_they_allocate():
    m = LFM2_8B_A1B
    cc = CacheConfig(num_pages=10240, page_size=16, state_slots=64)
    # six layers of pages, two kv heads to a 128-lane tile: 12,288 B a token
    assert cc.page_shapes(m) == ((6, 16, 4, 128), (6, 16, 4, 128))
    assert cc.page_bytes(m) == 16 * 12288
    assert cc.state_shape(m) == (18, 64, 2, 2048)
    assert cc.state_bytes(m) == 9_437_184
    assert cc.total_bytes(m) == 10240 * 16 * 12288 + 9_437_184
    fit = CacheConfig.fit_hbm(m, 100 * 16 * 12288 + 9_437_184 + 7,
                              state_slots=64)
    assert fit.num_pages == 100 and fit.state_slots == 64
    # a model without conv layers has no state pool, whatever the slots
    from helix_tpu.models.common import QWEN2_7B

    assert cc.state_shape(QWEN2_7B) is None and cc.state_bytes(QWEN2_7B) == 0
    assert cc.page_shapes(QWEN2_7B)[0] == (28, 16, 4, 128)
    cfg = tiny()
    small = CacheConfig(num_pages=12, page_size=8, dtype="float32",
                        state_slots=3)
    cache = PagedKVCache.create(cfg, small)
    assert cache.k_pages.shape == (3, 12, 8, 1, 128)
    assert cache.state.shape == (9, 3, 2, 64)
    with pytest.raises(ValueError, match="int8"):
        PagedKVCache.create(cfg, dataclasses.replace(small, dtype="int8"))


def test_packed_heads_are_the_same_attention():
    """Head width 64: two kv heads share a 128-lane tile of the pool and
    every query is zero-filled over its neighbour's lanes.  The packed call
    equals the plain one (a pool that stores ``[P, KVH, 64]``)."""
    T, H, KVH, D, P, N = 24, 8, 4, 64, 8, 10
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (T, H, D))
    kn = jax.random.normal(ks[1], (T, KVH, D))
    vn = jax.random.normal(ks[2], (T, KVH, D))
    kp = jax.random.normal(ks[3], (1, N, P, KVH, D))
    vp = jax.random.normal(ks[4], (1, N, P, KVH, D))
    t0 = jnp.asarray([0, 1, 9], jnp.int32)        # a decode row, two chunks
    qlen = jnp.asarray([1, 8, 15], jnp.int32)
    hist = jnp.asarray([13, 0, 20], jnp.int32)
    tables = jnp.asarray([[1, 2, 0], [0, 0, 0], [3, 4, 5]], jnp.int32)
    want = ragged_paged_attention_reference(
        q, kn, vn, kp, vp, 0, t0, qlen, hist, tables)
    packed = lambda a: a.reshape(1, N, P, KVH // 2, 2 * D)  # noqa: E731
    got = ragged_paged_attention(
        q, kn, vn, packed(kp), packed(vp), 0, t0, qlen, hist, tables,
        backend="reference")
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    qp, kk, vv = pack_heads(q, kn, vn, 2)
    assert qp.shape == (T, H, 128) and kk.shape == (T, 2, 128)
    # query head 2 belongs to kv head 1: the second block of packed head 0
    assert float(jnp.abs(qp[:, 2, :64]).sum()) == 0
    np.testing.assert_array_equal(np.asarray(qp[:, 2, 64:]),
                                  np.asarray(q[:, 2]))
    assert unpack_heads(qp, 2, KVH).shape == (T, H, D)
    np.testing.assert_array_equal(np.asarray(unpack_heads(qp, 2, KVH)),
                                  np.asarray(q))


@pytest.mark.parametrize("shape", ["decode_one_token", "chunk_and_decode"])
def test_pallas_kernel_at_width_64_in_interpret_mode(shape):
    """Both of the dense kernel's block shapes (one-token and 8-token query
    blocks) at the packed geometry, against the plain reference."""
    from helix_tpu.ops.paged_kernel import ragged_paged_attention_tpu

    H, KVH, D, P, N = 8, 4, 64, 16, 12
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    if shape == "decode_one_token":
        T, mq = 4, 1
        t0 = jnp.arange(4, dtype=jnp.int32)
        qlen = jnp.asarray([1, 1, 0, 1], jnp.int32)
        hist = jnp.asarray([17, 40, 0, 3], jnp.int32)
    else:
        T, mq = 40, None
        t0 = jnp.asarray([0, 8, 32, 40], jnp.int32)
        qlen = jnp.asarray([1, 24, 5, 0], jnp.int32)
        hist = jnp.asarray([33, 16, 0, 0], jnp.int32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 6], [0, 0, 0], [7, 8, 9]],
                         jnp.int32)
    q = jax.random.normal(ks[0], (T, H, D), jnp.float32)
    kn = jax.random.normal(ks[1], (T, KVH, D), jnp.float32)
    vn = jax.random.normal(ks[2], (T, KVH, D), jnp.float32)
    kp = jax.random.normal(ks[3], (2, N, P, KVH // 2, 2 * D), jnp.float32)
    vp = jax.random.normal(ks[4], (2, N, P, KVH // 2, 2 * D), jnp.float32)
    want = ragged_paged_attention(
        q, kn, vn, kp, vp, 1, t0, qlen, hist, tables, backend="reference")
    qp, kk, vv = pack_heads(q, kn, vn, 2)
    got = unpack_heads(ragged_paged_attention_tpu(
        qp, kk, vv, kp, vp, 1, t0, qlen, hist, tables, scale=D ** -0.5,
        max_q_len=mq, interpret=True), 2, KVH)
    live = np.zeros((T,), bool)
    for a, n in zip(np.asarray(t0), np.asarray(qlen)):
        live[a:a + n] = True
    err = np.abs(np.asarray(got) - np.asarray(want))[live].max()
    assert err < 2e-5, err


def test_prefix_cache_matches_only_up_to_a_boundary_with_a_state():
    pc = PrefixCache(stateful=True)
    hs = [bytes([i]) * 16 for i in range(5)]
    pc.adopt(hs, [11, 12, 13, 14, 15])
    assert pc.match_len(hs, pages_only=True) == 5
    assert pc.match_len(hs) == 0                  # pages alone: no resume
    pc.file_state(hs[2], "s3")
    pc.file_state(hs[2], "other")                 # first filing wins
    assert pc.match_len(hs) == 3 and pc.state_at(hs[2]) == "s3"
    pc.file_state(bytes([9]) * 16, "x")         # no such page: not filed
    assert pc.stats["states"] == 1
    pc.release([11, 12, 13, 14, 15])
    pc.evict(5)
    assert pc.stats["states"] == 0 and pc.match_len(hs) == 0
    assert "states" not in PrefixCache().stats


# ---- the engine, against the reference --------------------------------------


def _faults(params, seq):
    """What each fault reads against the reference on the same tokens."""
    want = np.asarray(reference.forward(params, HF, seq))
    faults = {
        "a conv state zeroed at the chunk boundary": dict(zero_state_at=16),
        "the expert bias left out": dict(expert_bias=False),
        "the q/k norm left out": dict(qk_norm=False),
        "top-1 for top-2": dict(top_k=1),
    }
    return {name: np.abs(np.asarray(reference.forward(
        params, HF, seq, **kw)) - want)[16:].max()
        for name, kw in faults.items()}


def test_chunked_prefill_then_decode_through_both_pools():
    """A 40-token prompt prefills in three chunks of 16 (the second and
    third continue from the conv state and the pages the one before left),
    then every decode step's logits, read through both pools, against the
    reference's full forward over the same tokens.  The tolerance is sharp:
    each fault of ISSUE 32 reads far over it."""
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(3))
    eng = _engine(cfg, params)
    prompt = tokens_of(40, seed=1)
    req = _req("a", prompt, n=8)
    with jax.default_matmul_precision("highest"):
        logits = _run(eng, [req], req)
        assert len(logits) >= 6
        for n, got in logits.items():
            seq = jnp.asarray(prompt + req.output_tokens[:n], jnp.int32)
            want = np.asarray(reference.forward(params, HF, seq)[-1])
            assert np.abs(got - want).max() < TOL, n
        seq = jnp.asarray(prompt + req.output_tokens, jnp.int32)
        for name, reads in _faults(params, seq).items():
            assert reads > 20 * TOL, (name, reads)
    assert eng.moe_dropped_tokens == 0 and eng.moe_routed_tokens > 0
    assert eng.num_state_snapshots >= 2          # 16, 32: chunk ends


def test_mixed_step_rows_equal_each_row_alone():
    """A long prompt chunks (three steps) while two other sequences decode
    in the same steps, on one flat token axis; a third request then takes a
    finished one's slot.  The watched request's logits are what it reads
    alone: no tap crosses a row boundary, no slot's state another's."""
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(4))
    prompt = tokens_of(44, seed=2)

    def run(slots, others):
        eng = _engine(cfg, params, slots=slots)
        watch = _req("x", prompt, n=5)
        reqs = [_req(f"o{i}", tokens_of(n, seed=9 + i), n=m)
                for i, (n, m) in enumerate(others)]
        out = _run(eng, reqs + [watch], watch)
        return watch.output_tokens, out, eng

    with jax.default_matmul_precision("highest"):
        alone, la, _ = run(1, [])
        crowded, lc, eng = run(3, [(5, 12), (11, 3), (7, 4)])
    assert alone == crowded
    assert eng.num_mixed_steps > 0
    assert _worst(la, lc) < TOL


def _decoding(cfg, params):
    eng = _engine(cfg, params, slots=3)
    eng.add_request(Request(
        id="d", prompt_tokens=tokens_of(7, seed=3),
        sampling=SamplingParams(max_tokens=40, temperature=0.8, seed=11,
                                frequency_penalty=0.3)))
    eng.step()
    eng.step()
    return eng


def test_a_chunk_and_the_decode_rows_share_one_pass():
    """A program with a prefill segment holds each loop body's products
    once: the expert layers' grouped products, the conv layers' in_proj."""
    import joint_pass

    cfg = tiny()
    eng = _decoding(cfg, init_params(cfg, jax.random.PRNGKey(4)))
    joint_pass.assert_one_forward(eng, 16, 1, "ragged_dot_general", "moe.experts")
    joint_pass.assert_one_forward(eng, 16, 1, "dot_general", "conv.in_proj")


def test_a_chunk_beside_decode_rows_is_the_chunk_then_the_decode_step():
    """Both pools: the chunk's rows and the decode rows read and write
    their own slots' conv states in one loop body."""
    import joint_pass

    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(4))

    def reqs():
        return [_req("s", tokens_of(6, seed=5), n=14),
                _req("x", tokens_of(44, seed=2), n=6)]

    with jax.default_matmul_precision("highest"):
        joint_pass.assert_mixed_is_chunk_then_decode(
            lambda **kw: _engine(cfg, params, slots=3, **kw), reqs, "x", TOL)


def test_a_wave_of_inert_rows_leaves_the_decode_state_and_the_states():
    """Every state row sits the wave out: ``DecodeState`` bit for bit, and
    no slot's conv state is written (the dummy row has no slot)."""
    import joint_pass

    cfg = tiny()
    eng = _decoding(cfg, init_params(cfg, jax.random.PRNGKey(4)))
    before = np.asarray(eng.cache.state)
    joint_pass.assert_inert_wave_keeps_decode_state(eng, 16)
    assert np.array_equal(before, np.asarray(eng.cache.state))
    assert before.any()


def test_a_wave_beside_running_rows_is_the_wave_then_the_decode_step():
    """Both pools: the running rows read and write their own slots' conv
    states inside the wave's pass, the slot being admitted is written by
    its prompt alone, and the row out of headroom keeps its state."""
    import joint_pass

    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(4))

    def reqs():
        return ([_req("a", tokens_of(7, seed=3), n=12),
                 _req("g", tokens_of(5, seed=4), n=9)],
                _req("short", tokens_of(6, seed=5), n=2),
                _req("late", tokens_of(11, seed=6), n=8))

    with jax.default_matmul_precision("highest"):
        joint_pass.assert_wave_is_wave_then_decode(
            lambda: _engine(cfg, params, slots=4), reqs, TOL)


def test_a_reused_slot_starts_from_no_state():
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(5))
    second = tokens_of(21, seed=7)
    with jax.default_matmul_precision("highest"):
        eng = _engine(cfg, params, slots=1, enable_prefix_cache=False)
        first = _req("a", tokens_of(30, seed=6))
        _run(eng, [first], first)
        b = _req("b", second)
        reused = _run(eng, [b], b)
        fresh_eng = _engine(cfg, params, slots=1, enable_prefix_cache=False)
        c = _req("c", second)
        fresh = _run(fresh_eng, [c], c)
    assert b.output_tokens == c.output_tokens
    assert _worst(reused, fresh) < TOL


def test_a_prefix_hit_resumes_from_the_filed_state():
    """Two prompts that share their first 37 tokens.  The second is served
    from the first one's pages AND the conv state filed at the boundary it
    resumes from (``cached_tokens > 0``), and reads what it reads cold."""
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(6))
    shared = tokens_of(37, seed=3)
    first, second = shared + [9, 8, 7], shared + tokens_of(9, seed=4)
    with jax.default_matmul_precision("highest"):
        eng = _engine(cfg, params)
        a = _req("a", first)
        _run(eng, [a], a)
        b = _req("b", second)
        hit = _run(eng, [b], b)
        cold_eng = _engine(cfg, params)
        c = _req("c", second)
        cold = _run(cold_eng, [c], c)
        first_read = min(hit)
        want = np.asarray(reference.forward(params, HF, jnp.asarray(
            second + b.output_tokens[:first_read]))[-1])
    # 37 shared tokens are four full pages; the states on file are at the
    # chunk ends 16 and 32 and at the first prompt's last boundary, 32
    assert b.cached_tokens == 32 and c.cached_tokens == 0
    assert eng.num_state_restores == 1 and eng.prefix_hits_shortened == 0
    assert b.output_tokens == c.output_tokens
    assert _worst(hit, cold) < TOL
    assert np.abs(hit[first_read] - want).max() < TOL


def test_a_hit_with_no_state_at_its_end_is_shortened_and_counted():
    """12 shared tokens: one full page matches, and no row of the first
    prompt ended on its boundary, 8 (the chunks end on 16 and 32): the hit
    is cut back to nothing, counted, and the request reads what it reads
    cold."""
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(7))
    shared = tokens_of(12, seed=5)
    with jax.default_matmul_precision("highest"):
        eng = _engine(cfg, params)
        a = _req("a", shared + tokens_of(20, seed=6))
        _run(eng, [a], a)
        b = _req("b", shared + tokens_of(11, seed=8))
        hit = _run(eng, [b], b)
        c = _req("c", b.prompt_tokens)
        cold = _run(_engine(cfg, params), [c], c)
    assert b.cached_tokens == 0 and eng.prefix_hits_shortened == 1
    assert eng.num_state_restores == 0
    assert _worst(hit, cold) < TOL


# ---- what is refused, by name -----------------------------------------------

REFUSED = {
    "int8 kv": dict(kv_cache_dtype="int8"),
    "adapters": dict(adapter_pool_slots=2),
    "speculation": dict(enable_spec_decode=True),
    "tiered residency": dict(ctx_hot_pages=4, host_pool_bytes=1 << 20),
    "the host tier": dict(host_pool_bytes=1 << 20),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_what_recurrent_state_is_not_served_with_is_refused(what):
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(UnsupportedForModel, match="recurrent state"):
        _engine(cfg, params, **REFUSED[what])


def test_a_mesh_is_refused_for_recurrent_state():
    from helix_tpu.engine.engine import refuse_unsupported

    class TwoDevices:
        devices = np.zeros((2,))

    with pytest.raises(UnsupportedForModel, match="mesh of more than one"):
        refuse_unsupported(tiny(), EngineConfig(), TwoDevices())
    # and the same table still refuses for latent attention
    from helix_tpu.models.common import DEEPSEEK_V2_LITE

    with pytest.raises(UnsupportedForModel, match="latent attention"):
        refuse_unsupported(DEEPSEEK_V2_LITE, EngineConfig(), TwoDevices())
    refuse_unsupported(DEEPSEEK_V2_LITE, EngineConfig(), None)


@pytest.mark.parametrize(
    "call", ["export_request", "export_prefill", "import_request",
             "kv_filestore"])
def test_paths_that_move_pages_without_the_state_are_refused(call):
    cfg = tiny()
    eng = _engine(cfg, init_params(cfg, jax.random.PRNGKey(0)))
    with pytest.raises(UnsupportedForModel, match="recurrent state"):
        if call == "kv_filestore":
            eng.kv_filestore = object()
        else:
            getattr(eng, call)("nobody")
    assert eng.kv_filestore is None
    assert eng.recurrent_state_bytes == 9 * 2 * 2 * 64 * 4
