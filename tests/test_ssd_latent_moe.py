"""Nemotron-H-style decoders on the CPU at a small size, float32, seeded
weights: layers that are ONE branch each, Mamba-2 mixers (a conv tail and a
float32 state a slot) beside attention with no rope (pages), ungated relu2
experts in a latent behind a sigmoid-and-bias router, of which the chip holds
one expert-parallel rank's.  The oracle is the benchmark's plain reference
(``benchmark/lib/reference_ssd_latent_moe_decoder.py``: the token-by-token
recurrence, explicit scores, a loop over experts, the PUBLISHED numbering);
the engine is compared by LOGITS."""

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
    reference_ssd_latent_moe_decoder as reference,
)
from helix_tpu.engine.engine import (  # noqa: E402
    Engine, EngineConfig, Request, SamplingParams, UnsupportedForModel,
)
from helix_tpu.models.common import (  # noqa: E402
    CATALOG, NEMOTRON3_SUPER_120B, ModelConfig,
)
from helix_tpu.models.llama import (  # noqa: E402
    forward, init_params, param_logical_axes, prefill_attn_fn,
)
from helix_tpu.models.mixers import STATE_MIXERS  # noqa: E402
from helix_tpu.ops import ssd  # noqa: E402
from helix_tpu.ops.grouped_matmul import relu2  # noqa: E402

HF = dict(
    model_type="nemotron_h", vocab_size=256, hidden_size=64,
    intermediate_size=48, moe_intermediate_size=48, num_hidden_layers=10,
    hybrid_override_pattern="MEM*EMEM*E", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=32,
    n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=8, expand=4,
    mamba_hidden_act="silu", mamba_proj_bias=False, use_conv_bias=True,
    use_bias=False, mlp_bias=False, mlp_hidden_act="relu2",
    n_routed_experts=4, published_n_routed_experts=16, held_experts=[4, 8],
    n_shared_experts=1, moe_shared_expert_intermediate_size=96,
    moe_latent_size=32, num_experts_per_tok=6, n_group=1, topk_group=1,
    norm_topk_prob=True, routed_scaling_factor=5, norm_eps=1e-5,
    layer_norm_epsilon=1e-5, residual_in_fp32=False, sliding_window=None,
    rope_theta=10000, partial_rotary_factor=1, tie_word_embeddings=False,
    max_position_embeddings=512, attention_bias=False,
)
# float32, the same mathematics through another order of operations (a state
# carried through 8-token blocks and single steps against a token-by-token
# scan; paged attention against explicit scores; a sorted grouped product
# against a loop over experts): measured 1e-6 and under on logits of spread
# 0.2
TOL = 1e-5


def tiny(**kw):
    cfg = ModelConfig.from_hf_config(dict(HF, **kw), name="tiny-nemotron")
    return dataclasses.replace(cfg, dtype="float32")


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(1))
    # norm gains off one, so that a gain left out is seen
    k = jax.random.PRNGKey(2)
    for key in (r.key for g in cfg.layer_runs() for r in g.runs):
        for name in ("attn_norm", "mlp_norm", "o_norm"):
            if name in params[key]:
                k, sub = jax.random.split(k)
                w = params[key][name]["weight"]
                params[key][name]["weight"] = w + 0.1 * jax.random.normal(
                    sub, w.shape)
        if "experts" in params[key]:
            # at std 0.02 over widths of 32 to 64 the routed sum is 1e-4 of
            # the residual and no fault of the expert path is seen
            lp = params[key]
            for leaf in (lp["fc1"], *lp["experts"].values()):
                leaf["weight"] = leaf["weight"] * 8.0
    return cfg, params


def tokens_of(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, size=n).tolist()


def _engine(cfg, params, **kw):
    ecfg = EngineConfig(**{**dict(
        max_decode_batch=3, page_size=8, num_pages=96, max_pages_per_seq=16,
        max_prefill_len=16, attn_backend="reference",
        enable_prefix_cache=False), **kw})
    return Engine(cfg, params, ecfg)


def _req(rid, prompt, n=5, **kw):
    return Request(id=rid, prompt_tokens=prompt, sampling=SamplingParams(
        max_tokens=n, temperature=0.0, **kw))


# ---- the configuration ------------------------------------------------------


def test_the_pattern_is_read_as_blocks_in_the_published_numbering():
    cfg = tiny(num_hidden_layers=8, hybrid_override_pattern="*EMEM-M*")
    assert cfg.num_layers == 8 and cfg.hybrid_pattern == "*EMEM-M*"
    assert cfg.mixers == ("attn", "mamba2", "mamba2", "mamba2", "attn")
    assert cfg.ffns == ("moe", "moe", "dense", "none", "none")
    assert (cfg.num_attn_layers, cfg.num_state_layers,
            cfg.num_moe_layers) == (2, 3, 2)
    assert cfg.state_mixer == "mamba2"
    assert not cfg.mlp_gated and not cfg.attn_rope
    assert cfg.hidden_act == "relu2" and cfg.moe_latent_size == 32
    assert cfg.held_experts == (4, 8) and cfg.num_experts == 16
    assert cfg.num_shared_experts == 2          # 96 wide, two experts' widths
    assert [(r.mixer, r.moe, r.ffn, r.count) for g in cfg.layer_runs()
            for r in g.runs] == [
        ("attn", True, True, 1), ("mamba2", True, True, 1),
        ("mamba2", False, True, 1), ("mamba2", False, False, 1),
        ("attn", False, False, 1)]
    # the fixture's: the period of the published pattern, twice
    assert [(g.reps, [(r.mixer, r.moe, r.ffn) for r in g.runs])
            for g in tiny().layer_runs()] == [
        (2, [("mamba2", True, True), ("mamba2", False, False),
             ("attn", True, True)])]


def test_the_preset_is_the_published_model():
    cfg = NEMOTRON3_SUPER_120B
    assert CATALOG[cfg.name] is cfg and cfg.num_layers == 88
    pat = cfg.hybrid_pattern
    assert (pat.count("M"), pat.count("*"), pat.count("E")) == (40, 8, 40)
    # every E follows a mixer; one M in a period stands before a * alone
    assert cfg.ffns.count("moe") == 40 and cfg.ffns.count("none") == 8
    assert cfg.mamba_inner == 8192 == 2 * cfg.hidden_size
    assert cfg.mamba_channels == 10240
    assert cfg.state_arrays() == (
        ((3, 10240), "bfloat16"), ((64, 128, 128), "float32"))
    kept = dataclasses.replace(
        cfg, num_layers=22, hybrid_pattern=pat[25:47], held_experts=(0, 128))
    assert kept.hybrid_pattern == "*EMEMEMEMEM*EMEMEMEMEM"
    assert [(g.reps, [(r.mixer, r.moe, r.ffn, r.count) for r in g.runs])
            for g in kept.layer_runs()] == [
        (2, [("attn", True, True, 1), ("mamba2", True, True, 4),
             ("mamba2", False, False, 1)])]
    assert kept.loop_bodies == 3


def test_every_key_of_the_published_config_is_read_or_named_as_unread():
    import inspect
    import json

    row = next(json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if "Nemotron-3-Super-120B-A12B-BF16" in l) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is None:
        pytest.skip("no catalog beside the guides")
    src = inspect.getsource(ModelConfig._nemotron_h_family) + (
        inspect.getsource(ModelConfig.from_hf_config))
    for key in row["config"]:
        assert f'"{key}"' in src or key in ModelConfig.NEMOTRON_H_UNREAD, key
    cfg = ModelConfig.from_hf_config(row["config"], name="x")
    assert dataclasses.replace(cfg, name=NEMOTRON3_SUPER_120B.name) == (
        NEMOTRON3_SUPER_120B)


REFUSED_CONFIGS = {
    "grouped_top_k": (dict(n_group=2), "n_group 1"),
    "topk_group": (dict(topk_group=2), "n_group 1"),
    "proj_bias": (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    "no_conv_bias": (dict(use_conv_bias=False), "use_conv_bias false"),
    "mamba_act": (dict(mamba_hidden_act="gelu"), "mamba_hidden_act"),
    "fp32_residual": (dict(residual_in_fp32=True), "residual_in_fp32"),
    "window": (dict(sliding_window=64), "sliding_window"),
    "expand": (dict(expand=2), "expand 2"),
    "groups": (dict(n_groups=3), "n_groups 3 does not divide"),
    "pattern_length": (dict(num_hidden_layers=9), "num_hidden_layers"),
    "held": (dict(held_experts=[0, 3]), "held_experts"),
    "lone_ffn": (dict(hybrid_override_pattern="E*EMEM-MEM"),
                 "follows no mixer"),
    "two_ffns": (dict(hybrid_override_pattern="*EEMEM-MEM"),
                 "follows no mixer"),
    "unknown_kind": (dict(hybrid_override_pattern="*EMEM-MEMX"), "'X'"),
}


@pytest.mark.parametrize("name", sorted(REFUSED_CONFIGS))
def test_what_is_not_served_is_refused_by_name(name):
    kw, what = REFUSED_CONFIGS[name]
    with pytest.raises(ValueError, match=what):
        tiny(**kw)


def test_a_block_with_no_feed_forward_allocates_no_such_weights(model):
    cfg, params = model
    runs = [r for g in cfg.layer_runs() for r in g.runs]
    for run in runs:
        names = set(params[run.key])
        ffn = {"mlp_norm", "w_up", "w_down", "router", "experts", "shared",
               "fc1", "fc2", "expert_bias"}
        assert bool(names & ffn) == run.ffn, (run, names)
        # no gate matrix anywhere: expert, shared expert, dense path
        assert "w_gate" not in names
        for sub in ("experts", "shared"):
            if sub in params[run.key]:
                assert set(params[run.key][sub]) == {"w_up", "w_down"}
    axes = param_logical_axes(cfg)
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=lambda a: isinstance(
            a, tuple)))
    # int8 weights have the tree's names too
    q = jax.eval_shape(lambda: init_params(
        cfg, jax.random.PRNGKey(0), int8=True))
    assert set(q) == set(params)


# ---- the forward pass and the engine against the reference -----------------


def test_the_forward_pass_is_the_reference(model):
    cfg, params = model
    toks = jnp.asarray([tokens_of(29, 3)])
    got, _ = forward(params, cfg, toks, jnp.arange(29)[None],
                     attn_fn=prefill_attn_fn)
    want = reference.forward(params, HF, toks[0])
    assert float(jnp.std(want)) > 0.1
    assert float(jnp.max(jnp.abs(got[0] - want))) < TOL
    # and the reference's faults part from it (the chip run reads all twelve
    # controls: ``chip_smoke_deepseek.py``; the selection bias counted into
    # the weights reads 2e-6 here, under TOL: a bias of std 0.03 renormalised
    # over six scores: PERF.md section 7)
    for fault in (dict(decay=False), dict(act="relu"),
                  dict(drop_expert=5)):
        other = reference.forward(params, HF, toks[0], **fault)
        assert float(jnp.max(jnp.abs(other - want))) > 10 * TOL, fault


def test_a_dense_layer_of_the_pattern_is_an_ungated_mlp():
    cfg = tiny(num_hidden_layers=3, hybrid_override_pattern="M-*")
    hf = dict(HF, num_hidden_layers=3, hybrid_override_pattern="M-*")
    params = init_params(cfg, jax.random.PRNGKey(7))
    assert set(params["run00"]) >= {"w_up", "w_down", "mlp_norm"}
    assert "w_gate" not in params["run00"]
    toks = jnp.asarray([tokens_of(12, 8)])
    got, _ = forward(params, cfg, toks, jnp.arange(12)[None],
                     attn_fn=prefill_attn_fn)
    want = reference.forward(params, hf, toks[0])
    assert float(jnp.max(jnp.abs(got[0] - want))) < TOL


def test_chunked_prefill_then_decode_through_the_engine_is_the_reference(
        model):
    cfg, params = model
    eng = _engine(cfg, params, max_decode_batch=2)
    # 21 tokens: chunks of 16 and 5 (two blocks of 8, then a short one that
    # continues from conv tail, state and pages); then decode steps beside
    # an idle slot
    req = _req("a", tokens_of(21, 1), 6)
    eng.add_request(req)
    logits = {}
    while eng.has_work():
        eng.step()
        n = len(req.output_tokens)
        if (n and n not in logits and req.slot is not None
                and eng.slots[req.slot] is req):
            logits[n] = np.asarray(eng.next_token_logits()[req.slot])
    seq = req.prompt_tokens + req.output_tokens
    want = np.asarray(reference.forward(params, HF, jnp.asarray(seq)))
    assert len(logits) >= 2
    for n, got in logits.items():
        assert np.abs(got - want[len(req.prompt_tokens) + n - 1]).max() < TOL
    # the host's account, through the record alone
    v = eng.mixer_values()
    assert v["layers"] == 4 and v["decode_rows"] > 0
    assert v["chunk_rows"] == 2                  # the prompt's two chunks
    # blocks of 8: 2 + 1, in each of four layers
    assert v["chunks"] == 3 * 4
    per_slot = 4 * (3 * cfg.mamba_channels + 8 * 32 * 16) * 4
    assert v["pool_bytes"] == 2 * per_slot
    assert v["state_bytes_touched"] == 2 * (
        v["decode_rows"] + v["chunk_rows"]) * per_slot


def test_attention_with_no_rope_is_invariant_to_a_shift_of_all_positions(
        model):
    cfg, params = model
    toks = jnp.asarray([tokens_of(19, 5)])
    pos = jnp.arange(19)[None]
    a, _ = forward(params, cfg, toks, pos, attn_fn=prefill_attn_fn)
    b, _ = forward(params, cfg, toks, pos + 1000, attn_fn=prefill_attn_fn)
    assert float(jnp.max(jnp.abs(a - b))) == 0.0
    roped = dataclasses.replace(cfg, attn_rope=True)
    c, _ = forward(params, roped, toks, pos, attn_fn=prefill_attn_fn)
    assert float(jnp.max(jnp.abs(a - c))) > 1e-5


# ---- the two forms of the state space against the recurrence ---------------


def _draw(T, H=8, P=32, G=2, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=-1.0, maxval=1.0))
    return (jax.random.normal(k[0], (T, H, P)), dt, dt * A,
            jax.random.normal(k[3], (T, G, N)),
            jax.random.normal(k[4], (T, G, N)))


def test_the_packed_state_is_the_state(model):
    h = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 32, 16))
    hp = ssd.pack_state(h)
    assert hp.shape == (3, 2, 16, 128)
    assert bool(jnp.all(ssd.unpack_state(hp, 32) == h))
    # heads 0..3 of row 0 side by side across the lanes, the state axis down
    assert bool(jnp.all(hp[1, 0, :, 32:64] == h[1, 1].T))


@pytest.mark.parametrize("T", [5, 8, 29])
def test_the_chunked_form_is_the_recurrence(T):
    args = _draw(T)
    h0 = jax.random.normal(jax.random.PRNGKey(9), (8, 32, 16))
    y, h = ssd.ssd_recurrence(*args, h0)
    y2, h2 = ssd.ssd_sequence(*args, h0, chunk=8)
    # float32 both sides, sums in another order: 1e-5 of the spread
    assert float(jnp.max(jnp.abs(y - y2))) < 1e-5 * float(jnp.std(y)) * 10
    assert float(jnp.max(jnp.abs(h - h2))) < 1e-5 * float(jnp.std(h)) * 10


def test_rows_that_start_mid_batch_and_a_row_shorter_than_a_block():
    """Three rows on one flat axis: 13 tokens from a state (a block and a
    short one), 3 tokens from zeros (shorter than a block) that start
    mid-axis, 9 tokens with no slot; a fourth row with no token.  Each is the
    recurrence on its own tokens from its own state, the pool written where
    a row has a slot and nowhere else."""
    T, N = 32, 4
    args = _draw(T, seed=3)
    t0 = jnp.asarray([0, 13, 16, 25])
    qlen = jnp.asarray([13, 3, 9, 0])
    hist = jnp.asarray([40, 0, 5, 7])
    slots = jnp.asarray([2, 0, N, 1])
    pool = ssd.pack_state(jax.random.normal(
        jax.random.PRNGKey(4), (2, N, 8, 32, 16)))
    y, new = ssd.ssd_rows(*args, t0, qlen, hist, slots, pool, 1, chunk=8)
    h_of = lambda p, s: ssd.unpack_state(p[1, s], 32)
    for r, (a, n, s, from_state) in enumerate(
            [(0, 13, 2, True), (13, 3, 0, False), (16, 9, None, False)]):
        h0 = h_of(pool, s) if from_state else jnp.zeros((8, 32, 16))
        want, h = ssd.ssd_recurrence(*(v[a:a + n] for v in args), h0)
        assert float(jnp.max(jnp.abs(y[a:a + n] - want))) < 1e-4, r
        if s is not None:
            assert float(jnp.max(jnp.abs(h_of(new, s) - h))) < 1e-4, r
    # what no row owns reads zeros, and is written nowhere
    assert not bool(jnp.any(y[25:]))
    assert bool(jnp.all(new[0] == pool[0]))
    assert bool(jnp.all(new[1, 1] == pool[1, 1]))
    assert bool(jnp.all(new[1, 3] == pool[1, 3]))


# the chunk kernel's cases: rows on one flat axis at a geometry it takes (packed
# rows of 128 lanes over a state of 128, the block of 128), (t0, tokens,
# tokens behind it, slot or None) a row, in a pool of two layers of four slots
_KERNEL_ROWS = {
    "three_blocks_from_a_state_with_a_short_last":
        dict(T=300, rows=[(0, 300, 40, 2)]),
    "from_zeros_mid_axis_and_shorter_than_a_block":
        dict(T=160, rows=[(0, 128, 7, 1), (128, 5, 0, 3)]),
    "a_row_with_no_slot": dict(T=140, rows=[(0, 140, 5, None)]),
    "a_row_with_no_token":
        dict(T=40, rows=[(0, 30, 9, 0), (30, 0, 7, 1)]),
    # seven entries in passes of two (``ssd.SLAB``): each row's blocks lie
    # either side of a pass's end, its state handed on
    "a_row_that_straddles_two_passes":
        dict(T=640, rows=[(0, 300, 0, 1), (300, 200, 6, 0), (500, 140, 0,
                                                            None)]),
    "nan_behind_a_rows_last_token":
        dict(T=200, rows=[(0, 70, 3, 2)], nan_from=70),
    "slots_out_of_order_and_one_left_idle":
        dict(T=150, rows=[(0, 20, 0, 3), (20, 130, 11, 0)]),
}


@pytest.mark.parametrize("case", sorted(_KERNEL_ROWS))
def test_the_chunk_kernel_is_the_recurrence(case):
    """``ssd_rows`` in its two halves with the chunk kernel in interpret
    mode: each row is the recurrence on its own tokens from its own state
    (zeros where it starts its sequence or has no slot), the pool written at
    ``layer`` where a row has a slot and tokens, and nowhere else; what no row
    owns reads zeros, whatever lies there."""
    H, P, G, N, S = 8, 64, 2, 128, 4
    spec = _KERNEL_ROWS[case]
    T, rows = spec["T"], spec["rows"]
    args = _draw(T, H, P, G, N, seed=11)
    if "nan_from" in spec:
        args = tuple(a.at[spec["nan_from"]:].set(jnp.nan) for a in args)
    pool = ssd.pack_state(jax.random.normal(
        jax.random.PRNGKey(12), (2, S, H, P, N)))
    col = lambda i, none=0: jnp.asarray(
        [none if r[i] is None else r[i] for r in rows], jnp.int32)
    y, new = ssd.ssd_rows(
        *args, col(0), col(1), col(2), col(3, S), pool, 1,
        backend="pallas", interpret=True)
    h_of = lambda p, s: ssd.unpack_state(p[1, s], P)
    written = set()
    for a, n, hist, slot in rows:
        if n == 0:
            continue
        h0 = (h_of(pool, slot) if slot is not None and hist > 0
              else jnp.zeros((H, P, N)))
        want, h = ssd.ssd_recurrence(*(v[a:a + n] for v in args), h0)
        # float32 both sides, sums in another order
        assert float(jnp.max(jnp.abs(y[a:a + n] - want))) < 1e-4 * float(
            jnp.std(want)), (a, n)
        if slot is not None:
            written.add(slot)
            assert float(jnp.max(jnp.abs(h_of(new, slot) - h))) < 1e-4 * (
                float(jnp.std(h))), (a, n)
    owned = np.zeros(T, bool)
    for a, n, _, _ in rows:
        owned[a:a + n] = True
    assert not bool(jnp.any(y[~owned]))
    assert bool(jnp.all(new[0] == pool[0]))
    for s in set(range(S)) - written:
        assert bool(jnp.all(new[1, s] == pool[1, s])), s


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_the_decode_step_is_the_recurrence(backend):
    """The CPU's packed step and the kernel (interpret mode, at a geometry
    it takes: rows of 128 lanes over a state of 128) against one token of the
    recurrence, with a row that sits the step out."""
    B, H, P, G, N = 4, 8, 64, 2, 128
    x, dt, la, Bm, Cm = _draw(B, H, P, G, N, seed=5)
    h = jax.random.normal(jax.random.PRNGKey(6), (B, H, P, N))
    pool = jnp.zeros((2, 6, H // 2, N, 128)).at[1, :B].set(ssd.pack_state(h))
    live = jnp.asarray([True, False, True, True])
    y, new = ssd.ssd_decode(x, dt, la, Bm, Cm, pool, 1, live,
                            backend=backend, interpret=True)
    want, h1 = ssd.ssd_step(x, dt, la, Bm, Cm, h)
    tol = 1e-5 * float(jnp.std(h1)) * 10
    assert float(jnp.max(jnp.abs(
        jnp.where(live[:, None, None], want, 0.0) - y))) < 1e-4
    for b in range(B):
        got = ssd.unpack_state(new[1, b], P)
        assert float(jnp.max(jnp.abs(
            got - (h1[b] if live[b] else h[b])))) < tol, b
    assert bool(jnp.all(new[0] == 0)) and bool(jnp.all(new[1, B:] == 0))


@pytest.mark.parametrize("steps", [1, 2, 4, 8])
def test_a_fused_window_on_the_kernel_is_the_recurrence_a_step_at_a_time(
        steps):
    """A window of ``steps`` decode steps on the kernel in interpret mode
    against the token-by-token recurrence, with a row that goes idle
    mid-window, a row that joins at step 2 and a row that is never live:
    every step's outputs; the pool untouched until the last step; then the
    state the recurrence leaves, every other slot and layer bit for bit."""
    B, H, P, G, N, L, S = 5, 8, 64, 2, 128, 2, 6
    pool = ssd.pack_state(jax.random.normal(
        jax.random.PRNGKey(6), (L, S, H, P, N)))
    lives = np.random.default_rng(steps).random((steps, B)) < 0.6
    lives[:, 4] = False
    lives[-1, 0], lives[:, 1] = False, True     # one leaves early, one stays
    lives[:2, 2], lives[2:, 2] = False, True    # one joins at step 2
    want, got = pool, pool
    # a window's tokens from a window before lie behind this one's
    pending = jax.tree.map(
        lambda a: a + (3 if a.dtype == jnp.float32 else True),
        ssd.window_zeros(L, B, H, P, G, N, 8))
    for i in range(steps):
        args = _draw(B, H, P, G, N, seed=100 * steps + i)
        live = jnp.asarray(lives[i])
        y0, want, _ = ssd.ssd_window_step(
            *args, want, None, 1, live, 0, True, backend="reference")
        y1, got, pending = ssd.ssd_window_step(
            *args, got, pending, 1, live, jnp.int32(i),
            jnp.asarray(i == steps - 1), backend="pallas", interpret=True)
        assert float(jnp.max(jnp.abs(y1 - y0))) < 1e-4 * float(jnp.std(y0))
        assert not np.any(np.asarray(y1)[~lives[i]])
        if i < steps - 1:
            assert np.array_equal(got, pool)
            assert np.array_equal(pending[3][1], lives[:i + 1].any(axis=0))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.std(want))
    idle = ~lives.any(axis=0)
    assert np.array_equal(got[0], pool[0])
    assert np.array_equal(got[1][:B][idle], pool[1][:B][idle])
    assert np.array_equal(got[1, B:], pool[1, B:])


def test_a_window_of_one_is_the_step_that_stands_alone_bit_for_bit():
    """On the kernel (interpret mode): a window with room for one token, and
    the last step of a longer window's room that is also its first, against
    ``ssd_decode``: the first the same call, the second the same sums."""
    B, H, P, G, N, L = 4, 8, 64, 2, 128, 2
    args = _draw(B, H, P, G, N, seed=5)
    pool = ssd.pack_state(jax.random.normal(
        jax.random.PRNGKey(6), (L, B + 2, H, P, N)))
    live = jnp.asarray([True, False, True, True])
    kw = dict(backend="pallas", interpret=True)
    y0, new0 = ssd.ssd_decode(*args, pool, 1, live, **kw)
    for room, exact in ((1, True), (8, False)):
        y1, new1, _ = ssd.ssd_window_step(
            *args, pool, ssd.window_zeros(L, B, H, P, G, N, room), 1, live,
            jnp.int32(0), jnp.asarray(True), **kw)
        if exact:
            assert np.array_equal(y1, y0) and np.array_equal(new1, new0)
        else:
            np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(new1, new0, rtol=1e-6, atol=1e-6)


# a model the kernels take in interpret mode: packed rows of 128 lanes over a
# state of 128, the chunked form at its block of 128, and no layer whose
# kernel has no interpret mode here (attention, the grouped expert product)
KERNEL_HF = dict(
    HF, num_hidden_layers=6, hybrid_override_pattern="M-M-M-",
    ssm_state_size=128, chunk_size=128)


@pytest.fixture(scope="module")
def kernel_model():
    cfg = dataclasses.replace(ModelConfig.from_hf_config(
        KERNEL_HF, name="tiny-nemotron-kernels"), dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(3))


def test_fused_windows_on_the_kernel_path_give_the_references_tokens(
        kernel_model, monkeypatch):
    """Through the engine with windows of up to 4 fused decode steps on
    ``backend="pallas"`` (the kernels in interpret mode): steps that read the
    state and write nothing, commits, chunk rows and decode rows that
    continue from what a window committed, and a reused slot, against the
    plain recurrence a step at a time (``backend="reference"``, no window)."""
    import functools

    import joint_pass
    from helix_tpu.ops import ssd_kernel

    cfg, params = kernel_model
    scenario = lambda eng: (
        joint_pass.windows_a_chunked_prompt_and_a_reused_slot(
            eng, _req, tokens_of))
    want = scenario(_engine(cfg, params))
    monkeypatch.setattr(ssd_kernel, "check_ssd_geometry", lambda *a: None)
    for name in ("ssd_rows", "ssd_window_step"):
        monkeypatch.setattr(
            ssd, name, functools.partial(getattr(ssd, name), interpret=True))
    eng = _engine(cfg, params, attn_backend="pallas", decode_steps_per_sync=4,
                  adaptive_sync_max_streams=0)
    got = scenario(eng)
    assert got == want and all(got.values())
    counts = eng.mixer_counts
    # windows were fused: a good part of the decode row-steps wrote nothing
    assert counts["state_writes"] < 0.75 * counts["decode_rows"]
    assert counts["chunk_rows"] >= 5 and eng.num_mixed_steps >= 1


def test_a_window_of_four_over_three_rows_writes_the_state_three_times(model):
    """The host's account of one fused launch: 12 decode row-steps, 3 writes
    of the state (``helix_ssd_state_writes_total``, and ``ssd_state_writes``
    on the launch's span and the flight record), and the bytes that moved:
    ``h`` read at every step and written once a row, the conv tail read and
    written at every step."""
    import joint_pass
    from helix_tpu.models.mixers import flight_fields

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
    assert [kw["ssd_state_writes"] for kw in seen] == [3]
    assert flight_fields(eng.kinds, {
        **eng.mixer_values(), **eng.mixer_gauges()}, before)[
            "ssd_state_writes"] == 3
    (c, _), (h, _) = cfg.state_arrays()
    c, h = (cfg.num_state_layers * int(np.prod(a)) * 4 for a in (c, h))
    assert added["state_bytes_touched"] == 12 * (c + h) + 12 * c + 3 * h
    # a step that stands alone writes what it reads
    eng2 = _engine(cfg, params)
    eng2.add_request(_req("x", tokens_of(5), 4))
    while eng2.has_work():
        eng2.step()
    assert eng2.mixer_counts["state_writes"] == (
        eng2.mixer_counts["decode_rows"]) > 0


def test_the_kernel_refuses_what_it_cannot_tile():
    from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry
    from helix_tpu.ops.ssd_kernel import check_ssd_geometry

    check_ssd_geometry(128, 64, 8, 128)              # the published
    check_ssd_geometry(128, 64, 8, 128, 128)         # ... and its block
    for bad in ((128, 64, 8, 64), (128, 48, 8, 128), (8, 64, 8, 128),
                (8, 64, 2, 128), (128, 64, 8, 128, 256),
                (128, 64, 8, 128, 64)):
        with pytest.raises(UnsupportedKernelGeometry):
            check_ssd_geometry(*bad)


# ---- the experts: ungated, in a latent, one rank's share -------------------


def test_the_ungated_grouped_product_is_ragged_dot():
    from helix_tpu.models.moe import experts_pallas, experts_xla
    from helix_tpu.ops.grouped_matmul import row_tile, visit_plan
    from helix_tpu.ops.quant import quantize_tensor

    X, K, F, rows = 8, 128, 256, 22 * 16
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    experts = {
        "w_up": quantize_tensor(
            jax.random.normal(k[0], (1, X, K, F)) * 0.05),
        "w_down": quantize_tensor(
            jax.random.normal(k[1], (1, X, F, K)) * 0.05)}
    e = jnp.sort(jax.random.randint(k[2], (rows,), 0, X + 1))
    sizes = jnp.sum(jax.nn.one_hot(e, X, dtype=jnp.int32), axis=0)
    xs = jax.random.normal(k[3], (rows, K))
    # 22 choices a token over the held groups: the tile the dispatch picks
    tm = row_tile(rows, X)
    plan = visit_plan(sizes, rows, tm)
    got = experts_pallas(xs, plan, tm, experts, 0, relu2, True)
    want = experts_xla(xs, sizes, jnp.minimum(e, X - 1), experts, 0, relu2)
    live = (e < X)[:, None]
    assert float(jnp.max(jnp.abs(jnp.where(live, got - want, 0.0)))) < 1e-4
    # and it is relu(x W_up) ** 2 W_down of a row's own expert, no gate
    w = lambda n, g: experts[n]["weight"][0, g].astype(jnp.float32) * (
        experts[n]["scale"][0, g])
    g = int(e[7])
    plain = jnp.square(jax.nn.relu(xs[7] @ w("w_up", g))) @ w("w_down", g)
    assert float(jnp.max(jnp.abs(want[7] - plain))) < 1e-4


def test_the_visit_plan_at_22_choices_a_token_and_128_groups():
    """The published counts: 64 decode rows x 22 choices over 512 experts, the
    128 held here: 1,408 assignments of which about a quarter stay, 2.75 a
    held expert; a 512-token chunk adds 2,816.  The row tile follows the rows
    an expert gets on average, and the plan's visits cover every routed row
    once."""
    from helix_tpu.ops.grouped_matmul import row_tile, visit_plan

    X, k = 128, 22
    for T in (64, 64 + 512):
        rows = T * k
        tm = row_tile(rows * X // 512, X)
        assert tm == (32 if T == 64 else 128)
        e = np.random.default_rng(T).integers(0, 512, size=rows)
        sizes = jnp.asarray(np.bincount(e[e < X], minlength=X))
        offsets, group, tile, count = visit_plan(sizes, rows, tm)
        n = int(count[0])
        assert n <= -(-rows // tm) + X - 1
        covered = np.zeros(rows, np.int32)
        for v in range(n):
            g, t = int(group[v]), int(tile[v])
            lo = max(int(offsets[g]), t * tm)
            hi = min(int(offsets[g + 1]), (t + 1) * tm)
            covered[lo:hi] += 1
        assert (covered[:int(sizes.sum())] == 1).all()
        assert not covered[int(sizes.sum()):].any()


def test_the_four_ranks_parts_add_up_to_the_uncut_layer(model):
    """Section 4's share test: the routed parts of the four ranks (experts [0,
    4), [4, 8), [8, 12), [12, 16)), summed IN THE LATENT, through ``W_fc2``
    once, plus the shared expert counted once, are the layer with all 16
    experts here."""
    cfg, _ = model
    whole = dataclasses.replace(cfg, held_experts=None)
    params = init_params(whole, jax.random.PRNGKey(3))
    hf_whole = dict(HF, n_routed_experts=16)
    del hf_whole["held_experts"]
    key, i = reference.homes(HF["hybrid_override_pattern"])[1]
    lp = params[key]
    u = jax.random.normal(jax.random.PRNGKey(4), (11, 64))
    with jax.default_matmul_precision("highest"):
        want = reference.expert_layer(u, lp, i, hf_whole, {})
        latent = 0.0
        for lo in (0, 4, 8, 12):
            part = jax.tree.map(lambda a: a, lp)
            part["experts"] = jax.tree.map(
                lambda a: a[:, lo:lo + 4], lp["experts"])
            latent = latent + reference.expert_layer(
                u, part, i, dict(HF, held_experts=[lo, lo + 4]),
                dict(shared=False, latent_out=False))
        shared = reference.expert_layer(
            u, lp, i, hf_whole, dict(drop_expert="all"))
        got = latent @ lp["fc2"]["weight"][i] + shared
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    # and the PROGRAM's layer of one rank is that rank's part
    from helix_tpu.models.llama import _layer

    rank = dataclasses.replace(cfg, held_experts=(8, 12))
    part = jax.tree.map(lambda a: a[i], lp)
    part["experts"] = jax.tree.map(lambda a: a[i, 8:12], lp["experts"])
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 11, 64))
    from helix_tpu.ops.norms import rms_norm

    ffn_only = {k: v for k, v in part.items()}
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, part["mlp_norm"]["weight"], 1e-5)[0]
        want = reference.expert_layer(
            x, jax.tree.map(lambda a: a[None], part), 0,
            dict(HF, held_experts=[8, 12]), {})
        # the block's second branch alone: the mixer's output projection of
        # zeros leaves the residual as it came
        ffn_only["out_proj"] = {
            "weight": jnp.zeros_like(part["out_proj"]["weight"])}
        out, _, _, stats = _layer(
            h, ffn_only, None, rank, jnp.arange(11)[None], None,
            prefill_attn_fn, mixer="mamba2")
    assert float(jnp.max(jnp.abs(out[0] - h[0] - want))) < 1e-5
    # 11 tokens x 6 choices: those to experts elsewhere are counted away
    assert float(stats[1] + stats[5]) == 66.0


# ---- the kind's record ------------------------------------------------------

REFUSED_SETTINGS = {
    "int8_kv": (dict(kv_cache_dtype="int8"), "kv_cache_dtype int8"),
    "adapters": (dict(adapter_pool_slots=2), "adapter_pool_slots"),
    "speculation": (dict(enable_spec_decode=True), "enable_spec_decode"),
    "tiered": (dict(ctx_hot_pages=4), "ctx_hot_pages"),
    "host_tier": (dict(host_pool_bytes=1 << 20), "host_pool_bytes"),
    "prefix_cache": (dict(enable_prefix_cache=True), "enable_prefix_cache"),
}


@pytest.mark.parametrize("name", sorted(REFUSED_SETTINGS))
def test_what_cannot_carry_the_state_is_refused_by_name(model, name):
    cfg, params = model
    kw, setting = REFUSED_SETTINGS[name]
    with pytest.raises(UnsupportedForModel, match=setting) as e:
        _engine(cfg, params, **kw)
    assert "Mamba-2" in str(e.value)


def test_a_mesh_and_the_calls_that_move_pages_are_refused_by_name(model):
    from helix_tpu.engine.engine import _refuse_call, refuse_unsupported

    cfg, _ = model

    class TwoDevices:
        devices = np.zeros((2,))

    with pytest.raises(UnsupportedForModel, match="mesh of more than one"):
        refuse_unsupported(cfg, EngineConfig(
            enable_prefix_cache=False), TwoDevices())
    for what in ("request export", "request import", "the KV filestore"):
        with pytest.raises(UnsupportedForModel, match=what) as e:
            _refuse_call(cfg, what)
        assert "Mamba-2" in str(e.value)
    kind = STATE_MIXERS["mamba2"]
    assert cfg.state_kind is kind
    assert {s for s, _ in kind.refusals} == {
        "multi_device", "int8_kv", "adapters", "spec_decode", "tiered",
        "host_tier", "prefix_cache"}
    assert [s.name for s in kind.series] == [
        "helix_ssd_chunks_total", "helix_recurrent_state_bytes",
        "helix_ssd_rows_total", "helix_ssd_rows_total",
        "helix_ssd_state_writes_total",
        "helix_ssd_chunk_rows_from_zeros_total",
        "helix_state_bytes_touched_total"]
    assert dict(kind.launch) == {
        "ssd_layers": "layers", "ssd_chunks": "chunks",
        "ssd_chunk_rows": "chunk_rows",
        "ssd_chunk_rows_from_zeros": "chunk_rows_from_zeros",
        "ssd_state_writes": "state_writes"}
    assert kind.flight == kind.launch
