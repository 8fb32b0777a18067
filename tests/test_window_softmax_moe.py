"""Mellum-style decoders on the CPU at a small size, float32, seeded weights:
a period that STARTS on a sliding-window layer (three of them, rings of K/V a
slot in the state pool, then one full-attention layer over pages), one count
of query heads for both kinds, the whole head rotated in both at one theta
with YaRN on the full layers alone, and in EVERY layer routed experts all held
behind a softmax router renormalised over the chosen: no shared expert, no
dense layer.  The oracle is the benchmark's plain reference
(``benchmark/lib/reference_window_softmax_moe_decoder.py``: whole-sequence
attention under explicit masks, a loop over experts); the engine is compared
by LOGITS.  The window is 8 and a page-table row 40 pages of 8: a 70-token
prompt passes the window eight times, a 330-token one a whole table row."""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import (  # noqa: E402
    reference_window_softmax_moe_decoder as reference,
)
from helix_tpu.engine.engine import (  # noqa: E402
    Engine, EngineConfig, Request, SamplingParams, UnsupportedForModel,
)
from helix_tpu.engine.kv_cache import CacheConfig  # noqa: E402
from helix_tpu.models.common import (  # noqa: E402
    CATALOG, MELLUM2_12B, ModelConfig,
)
from helix_tpu.models.llama import (  # noqa: E402
    forward, init_params, param_logical_axes, prefill_attn_fn,
)
from helix_tpu.ops.paged_kernel import chunk_query_block  # noqa: E402
from helix_tpu.ops.window_kernel import window_attention_tpu  # noqa: E402
import window_cases  # noqa: E402

FULL, SLIDE = "full_attention", "sliding_attention"
W = 8
HF = dict(
    model_type="mellum", vocab_size=256, hidden_size=64, intermediate_size=96,
    num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, max_position_embeddings=512, max_window_layers=0,
    attention_bias=False, hidden_act="silu", rms_norm_eps=1e-6,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    norm_topk_prob=True, tie_word_embeddings=False, sliding_window=W,
    use_sliding_window=True,
    rope_parameters={
        # positions pass the original context (32) inside the long prompt
        FULL: dict(rope_type="yarn", rope_theta=500000, factor=16,
                   original_max_position_embeddings=32, beta_fast=32,
                   beta_slow=1, attention_factor=1.2772588722239782),
        SLIDE: dict(rope_type="default", rope_theta=500000)},
    layer_types=[SLIDE, SLIDE, SLIDE, FULL], mlp_layer_types=["sparse"] * 4,
)
# float32, the same mathematics through another order of operations (a ring
# and pages against whole-sequence attention under a mask; a sorted grouped
# product against a loop over experts; a softmax over the top-k logits
# against the top-k of a softmax, renormalised): measured 6e-8 on logits of
# spread 0.16
TOL = 1e-5
# relative RMS of the logits' change.  Over a whole 70-token forward the least
# any fault reads at this size is the dropped attention factor's 1.5e-4 (plain
# rope on the full layer 1.9e-4, YaRN on the sliding layers 4.0e-4, a dropped
# expert 7.6e-4, an 8-bit ring 8.0e-4, no renormalisation 1.4e-3, all experts
# 2.0e-3, one key more 7.3e-3, no window 1.9e-2, a window of 4 2.4e-2); at a
# SINGLE decode step one dropped expert of eight reads as little as 7.7e-6 (a
# step whose own token does not choose it sees it through attention alone).
# The engine's own error is 1e-7: the limit lies a factor of 2.5 under the
# least fault and thirty over the engine
FAULT_LIMIT = 3e-6
FAULTS = [dict(no_window=True), dict(window=4), dict(window_off_by_one=True),
          dict(yarn_on_sliding=True), dict(plain_on_full=True),
          dict(drop_attention_factor=True), dict(no_renorm=True),
          dict(drop_expert=1), dict(drop_expert="all"), dict(ring_8bit=True)]
TABLE = 40          # pages a page-table row of the engines below holds


def tiny(**kw):
    cfg = ModelConfig.from_hf_config(dict(HF, **kw), name="tiny-mellum")
    return dataclasses.replace(cfg, dtype="float32")


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, init_params(cfg, jax.random.PRNGKey(1))


def tokens_of(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, size=n).tolist()


def _engine(cfg, params, **kw):
    ecfg = EngineConfig(**{**dict(
        max_decode_batch=3, page_size=8, num_pages=3 * TABLE + 1,
        max_pages_per_seq=TABLE, max_prefill_len=16,
        attn_backend="reference", enable_prefix_cache=False), **kw})
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
    return float(np.sqrt(np.mean((got - want) ** 2)) / want.std())


# ---- the model -----------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(window_cases.ROWS_OF_4_KV_HEADS))
def test_long_block_over_a_ring_of_1024_against_the_masked_reference(name):
    """The window kernel's chunk call at this family's layout (a query group
    of 8 over 4 kv heads, a ring of 1,024), in interpret mode against
    whole-sequence attention under explicit masks."""
    window_cases.held_to_the_plain_oracle(
        functools.partial(window_attention_tpu, interpret=True),
        window_cases.ROWS_OF_4_KV_HEADS[name])


@pytest.mark.parametrize("bucket,group,block", [
    (16, 8, 16), (64, 8, 64), (128, 8, 128), (512, 8, 128), (512, 6, 128),
    (512, 16, 64), (512, 1, 128), (12, 8, 8), (5, 8, 8)])
def test_the_block_follows_the_bucket_and_the_group(bucket, group, block):
    """About 1,024 query rows a kv head and product, never more tokens than
    the bucket holds, a multiple of 8; and a row makes as many blocks under
    the smallest bucket (the engine's are multiples of 8) that holds it as
    under the largest: what ``_window_account`` counts by."""
    assert chunk_query_block(bucket, group) == block
    for n in (1, 9, 64, 65, 129, 300, 512):
        if n <= bucket and bucket % 8 == 0:
            assert -(-n // block) == -(-n // chunk_query_block(512, group))


def test_catalog_entry_is_the_published_config():
    assert CATALOG[MELLUM2_12B.name] is MELLUM2_12B
    m = MELLUM2_12B
    assert (m.heads_of("attn"), m.heads_of("window")) == (32, 32)
    width, theta, scaling = m.rope_of("attn")
    assert (width, theta) == (128, 500000.0)
    assert dict(scaling) == {
        "rope_type": "yarn", "factor": 16, "beta_fast": 32, "beta_slow": 1,
        "original_max_position_embeddings": 8192,
        "attention_factor": 1.2772588722239782}
    # the kinds differ in SCALING, not in theta or width
    assert m.rope_of("window") == (128, 500000.0, None)
    assert (m.num_attn_layers, m.num_window_layers, m.loop_bodies) == (
        7, 21, 2)
    # a period that starts on a window layer, seven times: two loop bodies
    assert [(g.reps, [(r.key, r.mixer, r.moe, r.count, r.first, r.step)
                      for r in g.runs]) for g in m.layer_runs()] == [
        (7, [("run00", "window", True, 3, 0, 3),
             ("run01", "attn", True, 1, 0, 1)])]
    assert m.state_arrays() == (((1024, 4, 128), "bfloat16"),) * 2
    assert (m.num_experts, m.num_held_experts, m.num_experts_per_tok,
            m.expert_width, m.num_shared_experts, m.first_k_dense,
            m.num_moe_layers) == (64, 64, 8, 896, 0, 0, 28)
    assert not m.qk_norm and not m.attn_gate and m.held_experts is None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert ModelConfig.from_hf_config(
        row["config"], name=MELLUM2_12B.name) == MELLUM2_12B


def test_config_reads_the_keys(model):
    cfg, _ = model
    assert cfg.layer_types == ("window", "window", "window", "attn")
    assert (cfg.num_heads, cfg.window_num_heads, cfg.sliding_window) == (
        8, 0, W)
    assert (cfg.rotary_dim, cfg.window_rotary_dim) == (0, 0)
    assert dict(cfg.rope_scaling)["attention_factor"] == 1.2772588722239782
    assert cfg.window_rope_scaling is None
    assert cfg.window_rope_theta == cfg.rope_theta == 500000.0
    assert (cfg.moe_scoring, cfg.moe_expert_bias, cfg.moe_renormalize,
            cfg.routed_scaling_factor, cfg.num_shared_experts,
            cfg.first_k_dense, cfg.expert_capacity_factor) == (
        "softmax", False, True, 1.0, 0, 0, 0.0)
    assert cfg.state_mixer == "window" and cfg.num_state_layers == 3
    # norm_topk_prob false: the chosen probabilities as they are
    assert not tiny(norm_topk_prob=False).moe_renormalize
    # leading dense layers are read, as the sibling family's are
    dense = tiny(mlp_layer_types=["dense"] + ["sparse"] * 3)
    assert dense.first_k_dense == 1 and dense.ffns[0] == "dense"


@pytest.mark.parametrize("bad,match", [
    (dict(use_sliding_window=False), "use_sliding_window false"),
    (dict(mlp_layer_types=["sparse", "dense", "sparse", "sparse"]),
     "leading dense"),
    (dict(rope_parameters=dict(HF["rope_parameters"],
                               chunked_attention=dict(rope_theta=1e4))),
     "chunked_attention"),
    (dict(layer_types=[SLIDE, SLIDE, "linear_attention", FULL]),
     "linear_attention"),
    (dict(layer_types=[SLIDE, SLIDE, FULL]), "num_hidden_layers"),
    (dict(sliding_window=None), "need sliding_window"),
])
def test_what_the_family_does_not_serve_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match) as e:
        tiny(**bad)
    assert str(e.value).startswith("mellum:")


def test_full_layers_alone_need_no_window():
    """``use_sliding_window`` false is no fault where no sliding layer is
    listed: every layer a full one, pages alone."""
    cfg = tiny(layer_types=[FULL] * 4, use_sliding_window=False)
    assert cfg.state_mixer is None and cfg.num_attn_layers == 4


def test_trees_and_axes_name_every_tensor(model):
    cfg, params = model
    q8 = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), int8=True))
    names = lambda t: sorted(  # noqa: E731
        "/".join(str(getattr(p, "key", p)) for p in path[:-1])
        for path, _ in jax.tree_util.tree_flatten_with_path(t)[0])
    assert set(names(params)) == set(names(q8))
    axes = param_logical_axes(cfg)
    assert set(names(params)) == set(names(jax.tree.map(
        lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple))))
    assert sorted(k for k in params if k.startswith("run")) == [
        "run00", "run01"]
    assert params["run00"]["wq"]["weight"].shape == (3, 64, 8 * 16)
    assert params["run01"]["wq"]["weight"].shape == (1, 64, 8 * 16)
    assert params["run00"]["experts"]["w_gate"]["weight"].shape == (
        3, 8, 64, 32)
    assert params["run00"]["router"]["weight"].shape == (3, 64, 8)
    for run in ("run00", "run01"):
        for absent in ("attn_gate", "shared", "expert_bias", "q_norm",
                       "w_gate"):
            assert absent not in params[run], (run, absent)


def test_rings_stand_beside_a_page_pool_of_the_full_layer(model):
    cfg, params = model
    cc = CacheConfig(num_pages=3 * TABLE + 1, page_size=8,
                     max_pages_per_seq=TABLE, state_slots=3, dtype="float32")
    assert cc.page_shapes(cfg) == ((1, 8, 2, 16), (1, 8, 2, 16))
    assert cc.state_shapes(cfg) == (((3, 3, W, 2, 16), "float32"),) * 2
    eng = _engine(cfg, params)
    assert eng.cache.k_pages.shape == (1, 3 * TABLE + 1, 8, 2, 16)
    assert [a.shape for a in eng.cache.state] == [(3, 3, W, 2, 16)] * 2
    assert eng.recurrent_state_bytes == cc.state_bytes(cfg)
    # a page is the ONE full layer's: admission and kv_pages_used count it
    assert cc.page_bytes(cfg) == 2 * 1 * 8 * 2 * 16 * 4


def test_forward_without_a_cache_is_the_reference(model):
    cfg, params = model
    toks = jnp.asarray(tokens_of(70, 0))
    got, _ = forward(params, cfg, toks[None], jnp.arange(70)[None],
                     attn_fn=prefill_attn_fn)
    want = np.asarray(reference.forward(params, HF, toks))
    assert np.abs(np.asarray(got[0]) - want).max() < TOL
    assert want.std() > 0.05
    for fault in FAULTS:
        bad = np.asarray(reference.forward(params, HF, toks, **fault))
        assert _rel(bad, want) > FAULT_LIMIT, fault


def test_the_router_is_the_references(model):
    """``models/moe.py::route`` takes the top k of the logits and a softmax
    over them; the reference a softmax over all, its top k, renormalised:
    one function.  With ``norm_topk_prob`` false both keep the chosen
    probabilities as they are."""
    from helix_tpu.models.moe import moe_ffn, route

    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 21, 64))
    lp = params["run00"]
    router = lp["router"]["weight"][1]
    for norm in (True, False):
        c = dataclasses.replace(cfg, moe_renormalize=norm)
        w, idx = route(x[0], router, c)
        p = jax.nn.softmax(x[0] @ router, axis=-1)
        pw, pidx = jax.lax.top_k(p, 2)
        if norm:
            pw = pw / pw.sum(-1, keepdims=True)
        assert np.array_equal(np.asarray(idx), np.asarray(pidx))
        assert np.abs(np.asarray(w) - np.asarray(pw)).max() < 1e-6
        assert (np.abs(np.asarray(w).sum(-1) - 1) < 1e-6).all() == norm
        out, stats = moe_ffn(
            x, router, None, c, jax.nn.silu, return_stats=True,
            stacked_experts=(lp["experts"], 1), backend="reference")
        want = np.asarray(reference.expert_layer(
            x[0], lp, 1, dict(HF, norm_topk_prob=norm), {}))
        assert np.abs(np.asarray(out[0]) - want).max() < TOL
        # every assignment is here: 21 tokens x 2 choices, none away
        assert (float(stats[1]), float(stats[5])) == (42.0, 0.0)
        assert 2 <= float(stats[3]) <= 8 and 0 < float(stats[4]) <= 1


# ---- the engine ---------------------------------------------------------------


def test_prefill_chunks_then_decode_through_ring_and_pages_is_the_reference(
        model):
    """A 70-token prompt in five chunks (the ring wraps eight times in
    prefill, positions pass YaRN's original context) beside a second request
    that decodes through the chunks (mixed steps), then decode steps:
    next-token logits against the reference's full forward at every step,
    each fault over the limit at every step."""
    cfg, params = model
    eng = _engine(cfg, params)
    prompt = tokens_of(70, 0)
    req, other = _req("a", prompt, 12), _req("b", tokens_of(9, 1), 30)
    got = _run(eng, [other, req], req)
    assert len(got) >= 10 and eng.num_mixed_steps >= 3
    assert eng.mixer_counts["chunk_rows"] >= 5
    assert eng.mixer_counts["decode_rows"] >= 30
    assert len(prompt) > 8 * W
    # a sliding layer holds no page and a slot's bytes do not grow
    assert eng.recurrent_state_bytes // 3 == 3 * 2 * W * 2 * 16 * 4
    tok_bytes = 3 * 2 * 2 * 16 * 4
    counts = eng.mixer_counts
    assert counts["ring_bytes_read"] > 0 and (
        counts["ring_bytes_read"] % tok_bytes == 0)
    assert counts["state_bytes_touched"] % tok_bytes == 0
    eng._drain_moe_drops()
    # no held range: every assignment is routed here, none away
    assert eng.moe_routed_tokens > 0 and eng.moe_away_tokens == 0
    assert 1 <= eng.moe_experts_touched <= 8
    assert 0 < eng.moe_tile_fill_ratio <= 1
    seq = jnp.asarray(prompt + req.output_tokens)
    at = [len(prompt) + n - 1 for n in sorted(got)]
    mine = np.stack([got[n] for n in sorted(got)])
    want = np.asarray(reference.forward(params, HF, seq, rows=at))
    assert np.abs(mine - want).max() < TOL
    worst = max(_rel(m, w) for m, w in zip(mine, want))
    for kw in FAULTS:
        bad = np.asarray(reference.forward(params, HF, seq, rows=at, **kw))
        least = min(_rel(b, w) for b, w in zip(bad, want))
        assert least > FAULT_LIMIT > 10 * worst, (kw, least, worst)


def test_a_sequence_past_one_page_table_row_is_the_reference(model):
    """A prompt of 310 tokens and 8 more decoded: 40 pages of 8, the whole
    width of the engine's page table (as the cell's 8,576 tokens fill 536 of
    544), in twenty chunks: the full layer walks every page of the row and
    the rings have wrapped thirty-nine times."""
    cfg, params = model
    eng = _engine(cfg, params)
    prompt = tokens_of(310, 5)
    req = _req("a", prompt, 8)
    got = _run(eng, [req], req)
    assert len(prompt) + len(req.output_tokens) > (TABLE - 1) * 8
    assert eng.mixer_counts["chunk_rows"] == 20
    seq = jnp.asarray(prompt + req.output_tokens)
    at = [len(prompt) + n - 1 for n in sorted(got)]
    want = np.asarray(reference.forward(params, HF, seq, rows=at))
    mine = np.stack([got[n] for n in sorted(got)])
    assert len(at) >= 6 and np.abs(mine - want).max() < TOL
    # a prompt that cannot end inside the table is refused at the door
    assert "exceeds" in eng.validate_request(_req("b", tokens_of(320, 6)))


@pytest.mark.parametrize("n", [5, 8, 9, 16, 17, 40])
def test_any_prompt_length_against_the_reference(model, n):
    """Under the window, exactly it, one past it, a whole chunk, one past a
    chunk, far past: the first tokens' logits."""
    cfg, params = model
    prompt = tokens_of(n, n)
    req = _req("a", prompt, 4)
    got = _run(_engine(cfg, params), [req], req)
    seq = jnp.asarray(prompt + req.output_tokens)
    at = [n + k - 1 for k in sorted(got)]
    want = np.asarray(reference.forward(params, HF, seq, rows=at))
    assert np.abs(np.stack([got[k] for k in sorted(got)]) - want).max() < TOL


def test_a_mixed_step_gives_each_row_what_it_gets_alone(model):
    cfg, params = model
    prompt, short = tokens_of(40, 4), tokens_of(6, 5)
    eng = _engine(cfg, params)
    req = _req("a", prompt)
    both = _run(eng, [_req("s", short, 12), req], req)
    assert eng.num_mixed_steps >= 1
    ref = _req("a", prompt)
    alone = _run(_engine(cfg, params), [ref], ref)
    shared = sorted(set(both) & set(alone))
    assert len(shared) >= 4
    for n in shared:
        assert np.abs(both[n] - alone[n]).max() < TOL


def test_a_stale_ring_does_not_reach_the_next_sequence_in_its_slot(model):
    cfg, params = model
    for n in (5, 19):
        eng = _engine(cfg, params, max_decode_batch=1)
        first, second = _req("x", tokens_of(21, 6)), _req(
            "y", tokens_of(n, 7))
        _run(eng, [first], first)
        assert np.abs(np.asarray(eng.cache.state[0])).max() > 0
        got = _run(eng, [second], second)
        fresh_req = _req("y", tokens_of(n, 7))
        fresh = _run(_engine(cfg, params, max_decode_batch=1), [fresh_req],
                     fresh_req)
        assert second.output_tokens == fresh_req.output_tokens
        assert got and all(
            np.abs(got[k] - fresh[k]).max() < TOL for k in got)


REFUSED_SETTINGS = {
    "int8_kv": (dict(kv_cache_dtype="int8"), "kv_cache_dtype int8"),
    "adapters": (dict(adapter_pool_slots=2), "adapter_pool_slots"),
    "speculation": (dict(enable_spec_decode=True), "enable_spec_decode"),
    "tiered": (dict(ctx_hot_pages=4, host_pool_bytes=1 << 20),
               "ctx_hot_pages"),
    "host_tier": (dict(host_pool_bytes=1 << 20), "host_pool_bytes"),
    "prefix_cache": (dict(enable_prefix_cache=True), "enable_prefix_cache"),
}


@pytest.mark.parametrize("name", sorted(REFUSED_SETTINGS))
def test_what_a_ring_refuses_it_refuses_here_too(model, name):
    cfg, params = model
    kw, setting = REFUSED_SETTINGS[name]
    with pytest.raises(UnsupportedForModel, match=setting) as e:
        _engine(cfg, params, **kw)
    assert "a ring of K/V a slot (sliding-window attention)" in str(e.value)


@pytest.mark.parametrize("call", ["export_request", "export_prefill",
                                  "kv_filestore"])
def test_calls_that_move_pages_are_refused_by_name(model, call):
    cfg, params = model
    eng = _engine(cfg, params)
    with pytest.raises(UnsupportedForModel, match="a ring of K/V a slot"):
        if call == "kv_filestore":
            eng.kv_filestore = object()
        else:
            getattr(eng, call)("nobody")


# ---- spans, the flight record, /metrics --------------------------------------


def test_the_page_bytes_and_the_context_a_launch_counts_by_hand(model):
    """``helix_attn_page_bytes_read_total``: a launch's history pages (a live
    decode row's ``ceil(position / page)``, a chunk row's ``ceil(start /
    page)`` once a query block of the paged kernel's own size:
    the page kind's ``query_block``) times a page's K and V over the FULL
    layers alone; ``context_tokens``: the decode rows' positions and a chunk
    row's history and fresh tokens; ``attn_query_blocks``: the programs the
    paged kernel runs for the chunk rows.  All on the launch's span."""
    from helix_tpu.obs import trace as obs_trace

    cfg, params = model
    eng = _engine(cfg, params)
    page = eng.cache_cfg.page_bytes(cfg)
    assert page == 2 * 8 * 2 * 16 * 4           # ONE full layer's K and V
    seen = []
    orig = obs_trace.phase

    def phase(name, *a, **kw):
        if name == "helix.loop.launch":
            seen.append(kw)
        return orig(name, *a, **kw)

    obs_trace.phase = phase
    try:
        req = _req("a", tokens_of(37, 9), 4)
        _run(eng, [req], req)
    finally:
        obs_trace.phase = orig
    launches = [kw for kw in seen if kw["kind"] != "warmup"]
    # three chunks (16, 16, 5 tokens: 0, 2 and 4 pages of history; a bucket
    # of 16 is ONE block of the long form, where the 8-token block made 2),
    # then decode rows at positions 37..
    chunks = [kw for kw in launches if kw["prefill_rows"]]
    assert [kw["chunk_q_block"] for kw in chunks] == [16, 16, 8]
    assert [kw["chunk_q_block"] for kw in chunks] == [
        cfg.page_kind.query_block(cfg, kw["token_bucket"], 1)
        for kw in chunks]
    assert [kw["attn_page_bytes"] for kw in chunks] == [
        0, 2 * 1 * page, 4 * 1 * page]
    # (the first chunk has no history: the packed flash kernel runs it)
    assert [kw["attn_query_blocks"] for kw in chunks] == [0, 1, 1]
    assert eng.mixer_counts["attn_query_blocks"] == 2 and (
        eng.chunk_q_block == 8)
    assert [kw["context_tokens"] for kw in chunks] == [16, 32, 37]
    decodes = [kw for kw in launches if not kw["prefill_rows"]]
    assert decodes and decodes[0]["context_tokens"] == 37
    assert decodes[0]["attn_page_bytes"] >= 5 * page
    assert all(kw["attn_query_blocks"] == 0 and "chunk_q_block" not in kw
               for kw in decodes)
    assert eng.mixer_counts["attn_page_bytes_read"] == sum(
        kw["attn_page_bytes"] for kw in launches)
    assert all(kw["window_layers"] == 3 and kw["attn_layers"] == 1
               and "held_experts" not in kw and "mla_page_fetches" not in kw
               for kw in launches)
    assert eng.step_context_tokens == decodes[-1]["context_tokens"]


def test_flight_records_and_metrics_carry_the_new_series(model):
    """Through the serving loop and the HTTP surface's collector: the flight
    record carries the page bytes a step's programs walked and the live
    tokens its last launch attended over; ``/metrics`` renders the counter,
    the histogram, the window counters at this model's own bytes, and the
    routing gauges with no held range."""
    import threading

    from helix_tpu.serving.engine_loop import EngineLoop
    from helix_tpu.serving.openai_api import OpenAIServer
    from helix_tpu.serving.registry import ModelRegistry, ServedModel
    from helix_tpu.serving.tokenizer import ByteTokenizer

    cfg, params = model
    eng = _engine(cfg, params)
    loop = EngineLoop(eng, "tiny-mellum")      # never started: inline
    done = threading.Event()
    loop.submit(_req("m", tokens_of(37, 3), 5),
                lambda e: done.set() if e.finished else None)
    for _ in range(200):
        if done.is_set():
            break
        assert loop._pass()
    assert done.is_set()
    eng._drain_moe_drops()
    records = loop.flight.snapshot()["recent"]
    assert records and all(
        r["window_layers"] == 3 and r["held_experts"] == 0
        and r["attn_layers"] == 1 for r in records)
    assert sum(r["attn_page_bytes_read"] for r in records) == (
        eng.mixer_counts["attn_page_bytes_read"]) > 0
    assert max(r["context_tokens"] for r in records) >= 37
    assert max(r["window_rows_wrapped"] for r in records) == 1
    registry = ModelRegistry()
    registry.register(ServedModel(
        name="tiny-mellum", loop=loop, tokenizer=ByteTokenizer(),
        context_length=512))
    text = OpenAIServer(registry).obs.render()

    def value(series, label=""):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(series) and label in ln)
        return float(line.rsplit(" ", 1)[1])

    assert value("helix_attn_page_bytes_read_total{") == (
        eng.mixer_counts["attn_page_bytes_read"])
    # chunks of 16, 16 and 5 tokens: the two with history are one program
    # of the paged kernel each in the one full layer
    assert value("helix_attn_query_blocks_total{") == 2 == (
        eng.mixer_counts["attn_query_blocks"])
    assert {r["chunk_q_block"] for r in records} <= {0, 16, 8}
    assert records[-1]["chunk_q_block"] == 8
    assert value("helix_step_context_tokens_count{") == len(records)
    assert value("helix_step_context_tokens_sum{") == sum(
        r["context_tokens"] for r in records)
    assert value("helix_window_rows_total{", 'kind="chunk"') == 3
    assert value("helix_window_rows_total{", 'kind="decode"') >= 4
    # chunks of 16, 16 and 5 tokens: the two with history are one block of
    # the window kernel each in the three sliding layers
    assert value("helix_window_query_blocks_total{") == 2 * 3
    # the rings at this model's own bytes: three sliding layers of 2 kv heads
    tok_bytes = 3 * 2 * 2 * 16 * 4
    read = value("helix_window_ring_bytes_read_total{")
    assert read == eng.mixer_counts["ring_bytes_read"] > 0
    assert read % tok_bytes == 0
    assert value("helix_recurrent_state_bytes{") == eng.recurrent_state_bytes
    assert 1 <= value("helix_moe_experts_touched{") <= 8
    assert 0 < value("helix_moe_tile_fill_ratio{") <= 1
    assert value("helix_moe_routed_tokens_total{") == (
        eng.moe_routed_tokens) > 0
    assert "helix_moe_held_tokens_total" not in text
    assert "helix_mla_page_fetches_total" not in text


@pytest.mark.parametrize("rows,blocks", [
    ([(512, 0)], 0),                # a first chunk: the packed flash kernel
    ([(512, 8192)], 4),             # 64 under the 8-token block
    ([(20, 512)], 1), ([(130, 1024)], 2),
    ([(70, 0), (140, 200)], 1 + 2),  # one launch, one row of it with history
    ([], 0)])
def test_the_query_blocks_a_launch_counts_at_the_published_config(
        rows, blocks):
    """``helix_window_query_blocks_total`` by hand: the window kernel's
    programs for a launch's chunk rows, in each of the 21 sliding layers."""
    import types

    from helix_tpu.models.mixers import STATE_MIXERS

    cache_cfg = CacheConfig(dtype="bfloat16", num_pages=8, page_size=16,
                            max_pages_per_seq=8, state_slots=2)
    got = STATE_MIXERS["window"].account(
        MELLUM2_12B, cache_cfg,
        [types.SimpleNamespace(rem=n, start=h, slot=0) for n, h in rows],
        np.zeros(0, np.int64), 0)
    assert got["query_blocks"] == 21 * blocks
    assert got["chunk_rows"] == len(rows)


SCOPES = ("window.qkv", "window.kernel", "window.out", "attn.qkv",
          "attn.kernel", "attn.out", "moe.router", "moe.experts")


@pytest.fixture(scope="module")
def lowered_text(model):
    import joint_pass

    cfg, params = model
    eng = _engine(cfg, params)
    eng.add_request(_req("d", tokens_of(7, 3), 40, seed=11))
    eng.step()
    eng.step()
    fn, args = joint_pass.step_program(eng, 16, 1)
    return fn.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_step_carries_the_named_scope(lowered_text, scope):
    import re

    assert re.search(rf"[/\"]{re.escape(scope)}[/\"]", lowered_text), scope


def test_no_gate_and_no_shared_expert_in_the_lowered_step(lowered_text):
    for scope in ("window.gate", "attn.gate", "moe.shared"):
        assert scope not in lowered_text, scope
