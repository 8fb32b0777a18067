"""Laguna-style decoders on the CPU at a small size, float32, seeded weights:
sliding-window attention layers (a ring of K/V a slot in the state pool)
beside full-attention layers (pages), each kind with its own count of query
heads and its own rope, a sigmoid gate a head, one dense layer and then routed
experts of which the chip holds one expert-parallel rank's.  The oracle is the
benchmark's plain reference
(``benchmark/lib/reference_window_moe_decoder.py``: whole-sequence attention
under explicit masks, a loop over experts); the engine is compared by LOGITS.
The window is 8: a 37-token prompt passes it four times."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import reference_window_moe_decoder as reference  # noqa
from helix_tpu.engine.engine import (  # noqa: E402
    Engine, EngineConfig, Request, SamplingParams, UnsupportedForModel,
)
from helix_tpu.engine.kv_cache import CacheConfig  # noqa: E402
from helix_tpu.models.common import (  # noqa: E402
    CATALOG, LAGUNA_XS2, ModelConfig,
)
from helix_tpu.models.llama import (  # noqa: E402
    forward, init_params, param_logical_axes, prefill_attn_fn,
)
from helix_tpu.ops.attention import flash_attention, mha_reference  # noqa
from helix_tpu.ops.window import (  # noqa: E402
    ring_positions, window_attention_reference, write_ring,
)
from helix_tpu.ops.window_kernel import window_attention_tpu  # noqa: E402
import window_cases  # noqa: E402
from window_cases import ROWS  # noqa: E402

FULL, SLIDE = "full_attention", "sliding_attention"
W = 8
HF = dict(
    model_type="laguna", vocab_size=256, hidden_size=64, intermediate_size=96,
    num_hidden_layers=12, num_attention_heads=6, num_key_value_heads=2,
    head_dim=16, max_position_embeddings=512, attention_bias=False,
    rms_norm_eps=1e-6, num_experts=4, published_num_experts=16,
    held_experts=[0, 4], num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, tie_word_embeddings=False,
    gating=True, sliding_window=W,
    rope_parameters={
        FULL: dict(rope_theta=500000, rope_type="yarn", factor=8,
                   original_max_position_embeddings=16, beta_slow=1,
                   beta_fast=4, attention_factor=1.2,
                   partial_rotary_factor=0.5),
        SLIDE: dict(rope_type="default", rope_theta=10000,
                    partial_rotary_factor=1)},
    layer_types=[FULL, SLIDE, SLIDE, SLIDE] * 3,
    mlp_layer_types=["dense"] + ["sparse"] * 11,
    moe_apply_router_weight_on_input=False, moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[6, 8, 8, 8] * 3,
)
# float32, the same mathematics through another order of operations (a ring
# and pages against whole-sequence attention under a mask; a sorted grouped
# product against a loop over experts): measured 1e-7 on logits of spread 0.16
TOL = 1e-5
# relative RMS of the logits' change.  The least any fault reads at this size
# is full_rotary's 1.2e-4 (positions under 50 turn the upper dims little;
# window_off_by_one 5.6e-3, no_window 1.4e-2, drop_gate 1.5e-2, one_rope
# 3.9e-4, a dropped expert 5.7e-4); the engine's own error is 1e-6 and under:
# the limit lies a factor of ten from both
FAULT_LIMIT = 1e-5
FAULTS = ("no_window", "window_off_by_one", "full_rotary", "one_rope",
          "drop_gate")


def tiny(**kw):
    cfg = ModelConfig.from_hf_config(dict(HF, **kw), name="tiny-laguna")
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


# ---- the window call and the windowed flash form -----------------------------

@pytest.mark.parametrize("name", sorted(ROWS))
@pytest.mark.parametrize("form", ["reference", "kernel_in_interpret_mode"])
def test_window_call_against_the_masked_reference(name, form):
    def call(*args, max_q_len):
        if form == "reference":
            return window_attention_reference(*args)
        return window_attention_tpu(
            *args, interpret=True, max_q_len=max_q_len)

    window_cases.held_to_the_plain_oracle(call, ROWS[name])


@pytest.mark.parametrize("name,least", [
    ("decode_rows_on_both_sides_of_the_wrap", 1e-2),
    ("chunks_from_empty_across_a_boundary_and_wrapped", 1e-2),
    ("a_chunk_longer_than_a_block_and_not_a_multiple_of_it", 1e-3)])
def test_one_key_more_is_seen_by_the_window_call(name, least):
    """The control the chip's kernel phase runs: a window of ``W + 1`` lets
    in the ring row that holds the token ``W`` back.  The one-token block and
    the chunk call's long one (one key in 256 there)."""
    window, H, rows, mq, *more = ROWS[name]
    args, _ = window_cases.window_case(window, H, rows, 0, *more)
    right = window_attention_reference(*args)
    wrong = window_attention_reference(*args, window=window + 1)
    got = window_attention_tpu(*args, interpret=True, max_q_len=mq)
    # the first token of each row past the window (tokens outside every row
    # are unspecified)
    t0 = np.asarray(args[6])
    past = [int(t0[i]) for i, (n, h, _) in enumerate(rows)
            if n and h >= window]
    live = np.concatenate([np.arange(t0[i], t0[i] + n)
                           for i, (n, _, _) in enumerate(rows)])
    assert past and float(jnp.abs(got[live] - right[live]).max()) < 1e-5
    assert min(float(jnp.abs(got[i] - wrong[i]).max()) for i in past) > least


def test_ring_positions_and_the_write():
    pos = np.asarray(ring_positions(jnp.asarray([0, 3, 8, 29]), 8))
    assert (pos[0] < 0).all()
    assert pos[1].tolist()[:3] == [0, 1, 2] and (pos[1][3:] < 0).all()
    assert pos[2].tolist() == list(range(8))
    assert pos[3].tolist() == [24, 25, 26, 27, 28, 21, 22, 23]
    # a chunk of W replaces the ring, a shorter one rotates into it, of a
    # longer one the last W land; idle rows and padding write nothing
    L, S, KVH, D = 2, 3, 2, 4
    ring = jnp.full((L, S, 8, KVH, D), -1.0)
    new = jnp.arange(24, dtype=jnp.float32)[:, None, None] * jnp.ones(
        (24, KVH, D))
    t0, qlen = jnp.asarray([0, 8, 11]), jnp.asarray([8, 3, 11])
    hist, slots = jnp.asarray([16, 6, 5]), jnp.asarray([2, 0, 1])
    k, v = write_ring(ring, ring, 1, new, 2 * new, t0, qlen, hist, slots)
    assert float(k[0].max()) == -1.0                      # the other layer
    assert np.asarray(k[1, 2, :, 0, 0]).tolist() == list(range(8))
    assert np.asarray(k[1, 0, :, 0, 0]).tolist() == [
        10, -1, -1, -1, -1, -1, 8, 9]                  # positions 6, 7, 8
    # row 2: tokens 11..21 at positions 5..15; the last 8 (14..21) land
    assert np.asarray(k[1, 1, :, 0, 0]).tolist() == [
        14, 15, 16, 17, 18, 19, 20, 21]
    assert float(jnp.abs(v - jnp.where(k < 0, k, 2 * k)).max()) == 0.0


@pytest.mark.parametrize("window", [8, 24])
def test_windowed_flash_form_against_the_masked_reference(window):
    """Packed rows with positions that restart a segment, in interpret mode:
    the window beside the causal and segment masks, and the blocks wholly
    behind a query block's window skipped."""
    rng = np.random.default_rng(3)
    S, H, KVH, D = 64, 4, 2, 128
    q, k, v = (jnp.asarray(rng.standard_normal((1, S, h, D)), jnp.float32)
               for h in (H, KVH, KVH))
    seg = jnp.asarray([[1] * 37 + [2] * 20 + [0] * 7])
    pos = jnp.asarray([list(range(37)) + list(range(20)) + [0] * 7])
    kw = dict(causal=True, q_positions=pos, kv_positions=pos,
              q_segment_ids=seg, kv_segment_ids=seg, window=window)
    got = flash_attention(q, k, v, block_q=16, block_kv=16, interpret=True,
                          **kw)
    want = mha_reference(q, k, v, **kw)
    live = np.asarray(seg[0]) > 0
    assert float(jnp.abs(got - want)[0, live].max()) < 1e-5
    wide = mha_reference(q, k, v, **dict(kw, window=None))
    assert float(jnp.abs(wide - want)[0, live].max()) > 1e-2


# ---- the model -----------------------------------------------------------------


def test_catalog_entry_is_the_published_config():
    assert CATALOG[LAGUNA_XS2.name] is LAGUNA_XS2
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "Laguna-XS.2")
    assert ModelConfig.from_hf_config(
        row["config"], name=LAGUNA_XS2.name) == LAGUNA_XS2
    m = LAGUNA_XS2
    assert (m.heads_of("attn"), m.heads_of("window")) == (48, 64)
    assert m.rope_of("attn")[:2] == (64, 500000.0)
    assert m.rope_of("window") == (128, 10000.0, None)
    assert (m.num_attn_layers, m.num_window_layers, m.loop_bodies) == (
        10, 30, 4)
    assert [(g.reps, [(r.mixer, r.moe, r.count) for r in g.runs])
            for g in m.layer_runs()] == [
        (1, [("attn", False, 1)]),
        (9, [("window", True, 3), ("attn", True, 1)]),
        (1, [("window", True, 3)])]
    assert m.state_arrays() == (((512, 8, 128), "bfloat16"),) * 2


def test_config_reads_the_keys_and_the_cut(model):
    cfg, _ = model
    assert cfg.layer_types == ("attn", "window", "window", "window") * 3
    assert (cfg.num_heads, cfg.window_num_heads, cfg.sliding_window) == (
        6, 8, W)
    assert (cfg.rotary_dim, cfg.window_rotary_dim) == (8, 0)
    assert dict(cfg.rope_scaling)["attention_factor"] == 1.2
    assert cfg.window_rope_scaling is None and cfg.attn_gate
    assert (cfg.num_experts, cfg.held_experts, cfg.num_held_experts) == (
        16, (0, 4), 4)
    assert (cfg.moe_scoring, cfg.moe_expert_bias, cfg.moe_renormalize,
            cfg.routed_scaling_factor, cfg.num_shared_experts,
            cfg.first_k_dense) == ("sigmoid", False, True, 2.5, 1, 1)
    assert cfg.state_mixer == "window" and cfg.num_state_layers == 9


@pytest.mark.parametrize("bad,match", [
    (dict(mlp_layer_types=["sparse", "dense"] + ["sparse"] * 10),
     "leading dense"),
    (dict(moe_apply_router_weight_on_input=True), "router_weight_on_input"),
    (dict(gating="per-channel"), "gating"),
    (dict(num_attention_heads_per_layer=[6, 8, 8, 4] * 3), "one query head"),
    (dict(shared_expert_intermediate_size=48), "shared expert"),
    (dict(held_experts=[0, 3]), "held_experts"),
])
def test_what_the_block_does_not_do_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match):
        tiny(**bad)


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
    assert params["run01"]["wq"]["weight"].shape == (6, 64, 8 * 16)
    assert params["run02"]["wq"]["weight"].shape == (2, 64, 6 * 16)
    assert params["run01"]["attn_gate"]["weight"].shape == (6, 64, 8)
    assert params["run05"]["experts"]["w_gate"]["weight"].shape == (
        3, 4, 64, 32)
    assert "expert_bias" not in params["run01"]


def test_rings_stand_beside_a_page_pool_of_the_full_layers(model):
    cfg, params = model
    cc = CacheConfig(num_pages=96, page_size=8, max_pages_per_seq=16,
                     state_slots=3, dtype="float32")
    assert cc.page_shapes(cfg) == ((3, 8, 2, 16), (3, 8, 2, 16))
    # in the POOL's dtype, whatever the model's
    assert cc.state_shapes(cfg) == (((9, 3, W, 2, 16), "float32"),) * 2
    assert cc.state_bytes(cfg) == 2 * 9 * 3 * W * 2 * 16 * 4
    eng = _engine(cfg, params)
    assert eng.cache.k_pages.shape == (3, 96, 8, 2, 16)
    assert [a.shape for a in eng.cache.state] == [(9, 3, W, 2, 16)] * 2
    assert eng.recurrent_state_bytes == cc.state_bytes(cfg)


def test_forward_without_a_cache_is_the_reference(model):
    cfg, params = model
    toks = jnp.asarray(tokens_of(37, 0))
    got, _ = forward(params, cfg, toks[None], jnp.arange(37)[None],
                     attn_fn=prefill_attn_fn)
    want = np.asarray(reference.forward(params, HF, toks))
    assert np.abs(np.asarray(got[0]) - want).max() < TOL
    for fault in FAULTS:
        bad = np.asarray(reference.forward(params, HF, toks, **{fault: True}))
        assert _rel(bad, want) > FAULT_LIMIT, fault


def test_the_eight_shares_add_up_to_the_uncut_layer(model):
    """THE SHARE TEST: what the ranks give for one expert layer, the shared
    expert counted once, is the uncut reference's layer: the held-experts
    dispatch of ``models/moe.py`` at rank r against the reference's loop over
    ALL the experts."""
    from helix_tpu.models.moe import moe_ffn

    ranks, X, E, Fx = 4, 16, 64, 32
    cfg = tiny(num_experts=X, published_num_experts=X, held_experts=None)
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (1, 21, E))
    router = jax.random.normal(ks[1], (E, X)) * 0.5
    whole = {n: {"weight": jax.random.normal(k, (1, X) + shp) * 0.1}
             for n, k, shp in (("w_gate", ks[2], (E, Fx)),
                               ("w_up", ks[3], (E, Fx)),
                               ("w_down", ks[4], (Fx, E)))}
    lp = {"router": {"weight": router[None]}, "experts": whole}
    want = np.asarray(reference.expert_layer(
        x[0], lp, 0, dict(HF, held_experts=None), {"shared": False}))
    total = 0.0
    for r in range(ranks):
        lo, hi = r * X // ranks, (r + 1) * X // ranks
        held = dataclasses.replace(cfg, held_experts=(lo, hi))
        part = {n: {"weight": w["weight"][:, lo:hi]}
                for n, w in whole.items()}
        out, stats = moe_ffn(
            x, router, None, held, jax.nn.silu, return_stats=True,
            stacked_experts=(part, 0), backend="reference")
        total = total + np.asarray(out[0])
        # the reference's own cut gives the same part
        mine = np.asarray(reference.expert_layer(
            x[0], {"router": lp["router"], "experts": part}, 0,
            dict(HF, held_experts=[lo, hi]), {"shared": False}))
        assert np.abs(np.asarray(out[0]) - mine).max() < TOL
    assert np.abs(total - want).max() < TOL
    assert np.abs(want).max() > 0.1


# ---- the engine ---------------------------------------------------------------


def test_prefill_chunks_then_decode_through_ring_and_pages_is_the_reference(
        model):
    """A 37-token prompt in three chunks (the ring wraps four times in
    prefill) beside a second request SHORTER than the window (mixed steps),
    then decode steps while the short one crosses the window: next-token
    logits against the reference's full forward at every step, each fault
    over the limit at every step."""
    cfg, params = model
    eng = _engine(cfg, params)
    prompt = tokens_of(37, 0)
    req, other = _req("a", prompt, 12), _req("b", tokens_of(5, 1), 20)
    got = _run(eng, [req, other], req)
    assert len(got) >= 10 and eng.num_mixed_steps >= 1
    assert eng.mixer_counts["chunk_rows"] >= 4
    assert eng.mixer_counts["decode_rows"] >= 20
    assert len(other.prompt_tokens) < W < len(other.prompt_tokens) + len(
        other.output_tokens)
    # a sliding layer holds no page and a slot's bytes do not grow
    per_slot = eng.recurrent_state_bytes // 3
    assert per_slot == 9 * 2 * W * 2 * 16 * 4
    counts = eng.mixer_counts
    assert counts["ring_bytes_read"] > 0 and counts["state_bytes_touched"] > 0
    tok_bytes = 9 * 2 * 2 * 16 * 4
    assert counts["ring_bytes_read"] % tok_bytes == 0
    # every fresh token of both sequences was written once (the prompts'
    # rows of at most 16 tokens land their last 8)
    assert counts["state_bytes_touched"] % tok_bytes == 0
    eng._drain_moe_drops()
    assert eng.moe_routed_tokens > 0 and eng.moe_away_tokens > 0
    seq = jnp.asarray(prompt + req.output_tokens)
    at = [len(prompt) + n - 1 for n in sorted(got)]
    mine = np.stack([got[n] for n in sorted(got)])
    want = np.asarray(reference.forward(params, HF, seq, rows=at))
    assert np.abs(mine - want).max() < TOL
    worst = max(_rel(m, w) for m, w in zip(mine, want))
    for kw in [{f: True} for f in FAULTS] + [dict(drop_expert=1)]:
        bad = np.asarray(reference.forward(params, HF, seq, rows=at, **kw))
        least = min(_rel(b, w) for b, w in zip(bad, want))
        assert least > FAULT_LIMIT > 10 * worst, (kw, least, worst)


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


def _decoding(model):
    cfg, params = model
    eng = _engine(cfg, params)
    eng.add_request(_req("d", tokens_of(7, 3), 40, seed=11))
    eng.step()
    eng.step()
    return eng


def test_a_chunk_and_the_decode_rows_share_one_pass(model):
    import joint_pass

    eng = _decoding(model)
    joint_pass.assert_one_forward(eng, 16, 1, "dot_general", "mlp.down")
    joint_pass.assert_one_forward(eng, 16, 1, "dot_general", "window.out")
    joint_pass.assert_one_forward(eng, 16, 1, "dot_general", "attn.out")


def test_a_chunk_beside_decode_rows_is_the_chunk_then_the_decode_step(model):
    import joint_pass

    cfg, params = model

    def reqs():
        return [_req("s", tokens_of(6, 5), 14), _req("x", tokens_of(40, 4))]

    joint_pass.assert_mixed_is_chunk_then_decode(
        lambda **kw: _engine(cfg, params, **kw), reqs, "x", TOL)


def test_a_wave_of_inert_rows_leaves_the_decode_state_and_the_rings(model):
    import joint_pass

    eng = _decoding(model)
    before = [np.asarray(a) for a in eng.cache.state]
    joint_pass.assert_inert_wave_keeps_decode_state(eng, 16)
    for a, b in zip(before, eng.cache.state):
        assert np.array_equal(a, np.asarray(b))
    assert before[0].any() and before[1].any()


def test_a_wave_beside_running_rows_is_the_wave_then_the_decode_step(model):
    """The running rows' rings take one token inside the wave's pass; the row
    out of headroom keeps its ring bit for bit."""
    import joint_pass

    cfg, params = model

    def reqs():
        return ([_req("a", tokens_of(7, 3), 12, seed=11),
                 _req("g", tokens_of(5, 4), 9)],
                _req("short", tokens_of(6, 5), 2),
                _req("late", tokens_of(11, 6), 8))

    joint_pass.assert_wave_is_wave_then_decode(
        lambda: _engine(cfg, params, max_decode_batch=4), reqs, TOL)


def test_a_stale_ring_does_not_reach_the_next_sequence_in_its_slot(model):
    """A slot's ring is NOT cleared at admission: the finished sequence's
    rows are still there, and the mask by position hides them from a
    sequence shorter than the window and from one that wraps."""
    cfg, params = model
    for n in (5, 19):
        eng = _engine(cfg, params, max_decode_batch=1)
        first, second = _req("x", tokens_of(21, 6)), _req(
            "y", tokens_of(n, 7))
        _run(eng, [first], first)
        left = np.asarray(eng.cache.state[0])
        assert np.abs(left).max() > 0
        eng.add_request(second)
        eng.step()
        if n < W:
            # (not cleared: the rows the short prompt has not reached still
            # hold the first sequence's keys)
            assert np.array_equal(
                np.asarray(eng.cache.state[0])[:, 0, n + 1:],
                left[:, 0, n + 1:])
        got = _run(eng, [], second)
        fresh_req = _req("y", tokens_of(n, 7))
        fresh = _run(_engine(cfg, params, max_decode_batch=1), [fresh_req],
                     fresh_req)
        assert second.output_tokens == fresh_req.output_tokens
        assert got and all(
            np.abs(got[k] - fresh[k]).max() < TOL for k in got)


def test_idle_slots_and_padding_leave_the_rings_bit_for_bit(model):
    cfg, params = model
    eng = _engine(cfg, params)
    req = _req("a", tokens_of(13, 8), 5)      # 13 tokens in a rung of 16
    eng.add_request(req)
    while eng.has_work():
        eng.step()
    rings = [np.asarray(a) for a in eng.cache.state]
    assert all(np.any(a[:, 0]) for a in rings)
    assert not any(np.any(a[:, 1:]) for a in rings)


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
def test_what_cannot_carry_a_ring_is_refused_by_name(model, name):
    cfg, params = model
    kw, setting = REFUSED_SETTINGS[name]
    with pytest.raises(UnsupportedForModel, match=setting) as e:
        _engine(cfg, params, **kw)
    assert "a ring of K/V a slot (sliding-window attention)" in str(e.value)


def test_a_mesh_is_refused_by_name(model):
    from helix_tpu.engine.engine import refuse_unsupported

    cfg, _ = model

    class TwoDevices:
        devices = np.zeros((2,))

    with pytest.raises(UnsupportedForModel, match="a ring of K/V a slot"):
        refuse_unsupported(dataclasses.replace(cfg, held_experts=None),
                           EngineConfig(enable_prefix_cache=False),
                           TwoDevices())


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


def test_launch_record_carries_the_new_fields(model):
    from helix_tpu.obs import trace as obs_trace

    cfg, params = model
    eng = _engine(cfg, params)
    seen = []
    orig = obs_trace.phase

    def phase(name, *a, **kw):
        if name == "helix.loop.launch":
            seen.append(kw)
        return orig(name, *a, **kw)

    obs_trace.phase = phase
    try:
        req = _req("a", tokens_of(5, 9), 8)
        _run(eng, [req], req)
    finally:
        obs_trace.phase = orig
    assert seen and all(
        kw["window_layers"] == 9 and kw["attn_layers"] == 3
        and kw["held_experts"] == 4 and "deltanet_layers" not in kw
        and "conv_layers" not in kw for kw in seen)
    # the sequence passes the window while it decodes
    wrapped = [kw["window_rows_wrapped"] for kw in seen]
    assert wrapped[0] == 0 and wrapped[-1] == 1


# ---- spans, the flight record, /metrics --------------------------------------

SCOPES = ("window.qkv", "window.kernel", "window.gate", "window.out",
          "attn.qkv", "attn.kernel", "attn.gate", "attn.out", "moe.experts",
          "moe.shared")


@pytest.fixture(scope="module")
def lowered_text(model):
    import joint_pass

    fn, args = joint_pass.step_program(_decoding(model), 16, 1)
    return fn.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_step_carries_the_named_scope(lowered_text, scope):
    import re

    assert re.search(rf"[/\"]{re.escape(scope)}[/\"]", lowered_text), scope


def test_flight_records_and_metrics_carry_the_new_series(model):
    """Through the serving loop and the HTTP surface's collector: the step's
    flight record says how many window layers and held experts the model has
    and how many live rows have passed the window, and ``/metrics`` renders
    the rows that read their rings, the ring bytes read, the bytes written,
    and the rings' size."""
    import threading

    from helix_tpu.serving.engine_loop import EngineLoop
    from helix_tpu.serving.openai_api import OpenAIServer
    from helix_tpu.serving.registry import ModelRegistry, ServedModel
    from helix_tpu.serving.tokenizer import ByteTokenizer

    cfg, params = model
    eng = _engine(cfg, params)
    loop = EngineLoop(eng, "tiny-laguna")      # never started: inline
    done = threading.Event()
    loop.submit(_req("m", tokens_of(21, 3), 5),
                lambda e: done.set() if e.finished else None)
    for _ in range(200):
        if done.is_set():
            break
        assert loop._pass()
    assert done.is_set()
    eng._drain_moe_drops()
    records = loop.flight.snapshot()["recent"]
    assert records and all(
        r["window_layers"] == 9 and r["held_experts"] == 4
        and r["attn_layers"] == 3 and r["deltanet_layers"] == 0
        for r in records)
    assert max(r["window_rows_wrapped"] for r in records) == 1
    registry = ModelRegistry()
    registry.register(ServedModel(
        name="tiny-laguna", loop=loop, tokenizer=ByteTokenizer(),
        context_length=128))
    text = OpenAIServer(registry).obs.render()

    def value(series, label=""):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(series) and label in ln)
        return float(line.rsplit(" ", 1)[1])

    assert value("helix_window_rows_total{", 'kind="chunk"') == 2
    assert value("helix_window_rows_total{", 'kind="decode"') >= 4
    # chunks of 16 and 5 tokens: the second is one block of the window
    # kernel in each of the nine sliding layers
    assert value("helix_window_query_blocks_total{") == 9
    assert value("helix_recurrent_state_bytes{") == eng.recurrent_state_bytes
    assert value("helix_window_ring_bytes_read_total{") == (
        eng.mixer_counts["ring_bytes_read"]) > 0
    assert value("helix_state_bytes_touched_total{") == (
        eng.mixer_counts["state_bytes_touched"]) > 0
    assert value("helix_moe_held_tokens_total{") == eng.moe_routed_tokens > 0
    assert "helix_deltanet_rows_total" not in text
