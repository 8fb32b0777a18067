"""GLM-5-style decoders (``model_type: glm_moe_dsa``) on the CPU at a small
size, float32, seeded weights: latent attention behind a learned sparse-
attention indexer, the index key of every token cached in a pool of its own
beside the latent pool, each query attending the ``index_topk`` keys its
index scores choose.  The oracle is the benchmark's plain reference
(``benchmark/lib/reference_mla_dsa_moe_decoder.py``).

The choice is discrete, so the comparison has three parts (``compare``):
(a) the program's index scores against the reference's; (b) wherever the
program's set and the reference's differ, every position in one and not the
other scores within (a)'s tolerance of the reference's ``index_topk``-th; (c)
logits against the reference RUN ON THE PROGRAM'S OWN SETS of every layer."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import reference_mla_dsa_moe_decoder as reference  # noqa: E402
from helix_tpu.engine import engine as engine_mod  # noqa: E402
from helix_tpu.engine.engine import (  # noqa: E402
    Engine, EngineConfig, Request, SamplingParams, UnsupportedForModel,
)
from helix_tpu.engine.kv_cache import (  # noqa: E402
    CacheConfig, PagedKVCache, write_kv,
)
from helix_tpu.models.common import CATALOG, ModelConfig  # noqa: E402
from helix_tpu.models.llama import (  # noqa: E402
    forward, init_params, param_logical_axes, prefill_attn_fn,
)
from helix_tpu.models.moe import moe_ffn  # noqa: E402
from helix_tpu.ops import dsa  # noqa: E402
from helix_tpu.testing.dsa_probe import Probe  # noqa: E402

TOPK = 32
# (a) float32 both sides, the same sum in another order (a kernel's blocks,
# XLA's fusions), as a share of the scores' RMS: 1e-6 measured; bfloat16 index
# queries and keys miss by 3e-3 (``test_each_tolerance_catches_its_fault``)
SCORE_TOL = 1e-4
# (c) float32, the same mathematics on the same sets through another order
# of operations (absorbed against decompressed attention, a grouped product
# against a loop over experts): 2e-5 of logits of size 1; bfloat16
# activations miss by 1e-3 (logits of size 0.3), a dropped selection by more
LOGIT_TOL = 5e-5


def tiny(**kw):
    base = dict(
        vocab_size=300, hidden_size=64, num_layers=3, num_heads=4,
        num_kv_heads=4, head_dim=24, intermediate_size=96,
        rope_theta=10000.0, rms_norm_eps=1e-5, dtype="float32",
        max_position_embeddings=512, num_experts=8, num_experts_per_tok=3,
        expert_capacity_factor=0.0, moe_intermediate_size=32,
        num_shared_experts=1, first_k_dense=1, moe_renormalize=True,
        routed_scaling_factor=2.5, moe_scoring="sigmoid",
        moe_expert_bias=True, kv_lora_rank=32, q_lora_rank=48,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        index_heads=4, index_head_dim=16, index_topk=TOPK,
        name="tiny-mla-dsa-moe",
    )
    base.update(kw)
    return ModelConfig(**base)


def hf_of(cfg):
    """The Hugging Face keys the reference reads, from a ModelConfig."""
    return {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "kv_lora_rank": cfg.kv_lora_rank, "q_lora_rank": cfg.q_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.rms_norm_eps,
        "rope_parameters": {"rope_theta": cfg.rope_theta,
                            "rope_type": "default"},
        "index_n_heads": cfg.index_heads,
        "index_head_dim": cfg.index_head_dim,
        "index_topk": cfg.index_topk,
        "first_k_dense_replace": cfg.first_k_dense,
        "n_routed_experts": cfg.num_held_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.moe_renormalize,
        "held_experts": cfg.held_experts,
    }


def tokens_of(n, seed=0, vocab=300):
    return np.random.default_rng(seed).integers(1, vocab, size=n).tolist()


# The engines below share TWO configurations, so that a step program is
# traced and compiled once a shape for the module and not once a test
# (``_build_ragged_step_fn`` is keyed by the configuration, its name too): one
# whose programs are traced WITH ``ops.dsa.PROBE`` set, which only the tests
# that take ``probe`` run (the callback looks the probe up when it is CALLED:
# each test reads its own), and one traced without.  Weights are an engine's
# own: they are no part of the key.
PROBED = tiny(name="tiny-dsa-probed")
UNPROBED = tiny(name="tiny-dsa")


@pytest.fixture(scope="module")
def model():
    engine_mod._build_ragged_step_fn.cache_clear()
    yield PROBED, init_params(PROBED, jax.random.PRNGKey(7))
    engine_mod._build_ragged_step_fn.cache_clear()


@pytest.fixture
def probe(model):
    dsa.PROBE = Probe()
    yield dsa.PROBE
    dsa.PROBE = None


def _engine(cfg, params, **kw):
    ecfg = EngineConfig(
        max_decode_batch=kw.pop("slots", 2), page_size=16, num_pages=64,
        max_pages_per_seq=16, max_prefill_len=kw.pop("chunk", 16),
        attn_backend="reference",
        enable_prefix_cache=kw.pop("enable_prefix_cache", False), **kw)
    return Engine(cfg, params, ecfg)


def _drive(eng, reqs):
    """Run to the end; every request's logits after each step it decoded in
    (``{id: {tokens out so far: logits}}``) and its first page."""
    logits, first = {r.id: {} for r in reqs}, {}
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            eng.add_request(r)
        while eng.has_work():
            eng.step()
            live = [r for r in reqs if r.output_tokens and r.slot is not None
                    and eng.slots[r.slot] is r]
            if not live:
                continue
            got = np.asarray(eng.next_token_logits())
            for r in live:
                first[r.id] = int(eng._page_tables[r.slot][0])
                logits[r.id][len(r.output_tokens)] = got[r.slot]
    return logits, first


def compare(cfg, params, probe, req, logits, first):
    """The three-part comparison of one finished request; returns what it
    measured."""
    hf = hf_of(cfg)
    seq = jnp.asarray(req.prompt_tokens + req.output_tokens, jnp.int32)
    n = len(seq)
    # the reference ON THE PROGRAM'S OWN SETS gives the scores the program's
    # are held to and, by its own choice from them, the sets (at float32
    # the two never part; on the chip a near-tie chosen otherwise moves the
    # next layer's scores, so only matched hidden states compare)
    sel = probe.selection(first, cfg.num_layers, n, TOPK)
    on_sets, ref_scores, _ = reference.forward(
        params, hf, seq, selection=sel, want="index")
    ref_sets = [np.asarray(reference.choose(s, TOPK)) for s in ref_scores]
    ref_scores = [np.asarray(s) for s in ref_scores]
    want = reference.forward(params, hf, seq)
    worst_score, differ, sparse = 0.0, 0, 0
    tri = np.tril(np.ones((n, n), bool))
    rms = [float(np.sqrt(np.mean(s[tri] ** 2))) for s in ref_scores]
    for (f, l, p), sc in probe.scores.items():
        if f != first or p >= n:
            continue
        ref = ref_scores[l][p, :p + 1] / rms[l]
        sc = sc / rms[l]
        ok = np.isfinite(sc)
        assert ok.all(), (l, p)
        # (a)
        worst_score = max(worst_score, np.abs(sc - ref).max())
        # (b)
        mine, theirs = set(probe.sets[(f, l, p)].tolist()), set(
            np.nonzero(ref_sets[l][p])[0].tolist())
        assert len(mine) == min(p + 1, TOPK), (l, p, len(mine))
        sparse += p + 1 > TOPK
        if mine != theirs:
            differ += 1
            kth = np.sort(ref)[-TOPK]
            for s in mine ^ theirs:
                assert abs(ref[s] - kth) <= SCORE_TOL, (l, p, s)
    assert worst_score < SCORE_TOL, worst_score
    # (c)
    at = sorted(logits)
    rows = [len(req.prompt_tokens) + k - 1 for k in at]
    on_sets = np.asarray(on_sets)[rows]
    got = np.stack([logits[k] for k in at])
    worst_logit = np.abs(got - on_sets).max()
    assert worst_logit < LOGIT_TOL, worst_logit
    return dict(score=worst_score, logit=worst_logit, differ=differ, rms=rms,
                sparse=sparse, rows=rows, got=got, sel=sel, seq=seq,
                own=np.asarray(want)[rows])


def _req(rid, n, out, seed):
    return Request(id=rid, prompt_tokens=tokens_of(n, seed=seed),
                   sampling=SamplingParams(max_tokens=out, temperature=0.0))


# what each case runs: (requests as (prompt, out), engine keywords, which
# kinds of row must have chosen past ``index_topk``)
CASES = {
    # two chunks and eight steps, never more than 28 keys: every query
    # attends all it has; the index keys are cached all the same
    "never_past_topk": ([(20, 8)], dict(), set()),
    # ONE cold chunk of 48 tokens in a bucket of 64: queries 32..47 choose
    "a_chunk_inside_which_queries_pass_it": (
        [(48, 6)], dict(chunk=64), {"chunk", "decode"}),
    # two hundred tokens in seven chunks of 32: the second on has history
    # past 32 keys
    "chunks_with_history_past_it": (
        [(200, 6)], dict(chunk=32), {"chunk", "decode"}),
    # a short and a long decode row in the same steps
    "a_short_and_a_long_decode_row": (
        [(10, 12), (80, 12)], dict(), {"chunk", "decode"}),
    # a fused window of four steps crosses 32 keys inside it
    "the_fused_window_crosses_it": (
        [(27, 12)], dict(decode_steps_per_sync=4), {"decode"}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_through_both_pools_against_the_reference(
        case, model, probe):
    sizes, kw, kinds = CASES[case]
    cfg, params = model
    eng = _engine(cfg, params, **kw)
    reqs = [_req(f"r{i}", n, out, seed=10 + i)
            for i, (n, out) in enumerate(sizes)]
    logits, first = _drive(eng, reqs)
    seen = set()
    for r in reqs:
        assert len(logits[r.id]) >= 2
        m = compare(cfg, params, probe, r, logits[r.id], first[r.id])
        seen |= {probe.kinds[at] for at in probe.scores
                 if at[0] == first[r.id] and at[2] + 1 > TOPK}
        if not kinds:
            assert m["sparse"] == 0
    assert seen == kinds, seen
    # the host's account of the same launches
    c = eng.mixer_counts
    assert c["keys_selected"] <= c["keys_scored"] or not kinds
    assert (c["rows_decode_sparse"] > 0) == ("decode" in kinds)
    assert (c["rows_chunk_sparse"] > 0) == ("chunk" in kinds)


def test_each_tolerance_catches_its_fault(model, probe):
    """What the three tolerances are FOR, on one request (100 tokens in
    chunks of 16, six steps): bfloat16 index queries and keys in a float32
    configuration fail (a); bfloat16 products in place of float32 ones fail
    (c); so does dropping the selection (attending everything); and the
    reference's OWN sets give the same logits here (float32 sides do not
    part on a near-tie), so (b) had nothing to forgive."""
    cfg, params = model
    eng = _engine(cfg, params)
    req = _req("f", 100, 6, seed=10)
    logits, first = _drive(eng, [req])
    m = compare(cfg, params, probe, req, logits["f"], first["f"])
    assert m["differ"] == 0 and m["sparse"] > 100
    hf = hf_of(cfg)
    _, low, _ = reference.forward(params, hf, m["seq"], want="index",
                                  index_bf16=True)
    _, own, _ = reference.forward(params, hf, m["seq"], want="index")
    tri = np.tril(np.ones(own[0].shape, bool))
    assert min(np.abs(np.asarray(a) - np.asarray(b))[tri].max() / r
               for a, b, r in zip(low, own, m["rms"])) > 10 * SCORE_TOL
    for fault in ("act_bf16", "no_selection"):
        off = np.asarray(reference.forward(
            params, hf, m["seq"], rows=m["rows"], selection=m["sel"],
            **{fault: True}))
        assert np.abs(off - m["got"]).max() > 10 * LOGIT_TOL, fault
    assert np.abs(m["own"] - m["got"]).max() < LOGIT_TOL


def test_the_program_without_the_probe_gives_the_probed_runs_logits(
        model, probe):
    """The comparisons above read a program traced WITH ``ops.dsa.PROBE``
    set (a ``jax.debug.callback`` a pass); what serves is traced without.
    The same request through both (100 tokens in chunks of 32, then six
    steps: chunk and decode rows past ``index_topk``): the same tokens, the
    same logits."""
    cfg, params = model
    runs = []
    for probed in (True, False):
        if not probed:
            dsa.PROBE = None
        eng = _engine(cfg if probed else UNPROBED, params, chunk=32)
        req = _req("u", 100, 6, seed=10)
        logits, _ = _drive(eng, [req])
        runs.append((req.output_tokens, logits["u"],
                     eng.mixer_counts["rows_decode_sparse"]))
    assert len(probe.scores) > 0 and runs[0][2] == runs[1][2] > 0
    assert runs[0][0] == runs[1][0]
    assert sorted(runs[0][1]) == sorted(runs[1][1])
    for k, got in runs[0][1].items():
        np.testing.assert_array_equal(got, runs[1][1][k])


def test_plain_forward_chooses_as_the_reference_does():
    """The model's plain forward pass (no pool): the same scores, the same
    choice, float32 both sides."""
    import functools

    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(1))
    toks = jnp.asarray([tokens_of(70)], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, toks, jnp.arange(70)[None],
                         attn_fn=functools.partial(prefill_attn_fn, cfg=cfg))
    want = reference.forward(params, hf_of(cfg), toks[0])
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < LOGIT_TOL
    dense = reference.forward(params, hf_of(cfg), toks[0], no_selection=True)
    assert np.abs(np.asarray(dense) - np.asarray(want)).max() > 1e-3


def _chunk_case(R, T, S, t0, q_len, hist, seed=0, ties=True):
    """A chunk's inputs: index scores (rounded to halves, so that a
    threshold is tied) of ``T`` flat queries against ``R`` rows' ``S``
    history positions and against each other, rows ``[t0, t0 + q_len)``
    behind ``hist`` cached keys."""
    rng = np.random.default_rng(seed)
    sc_h = rng.normal(size=(R, T, S)).astype(np.float32)
    sc_f = rng.normal(size=(T, T)).astype(np.float32)
    if ties:
        sc_h, sc_f = np.round(sc_h * 2) / 2, np.round(sc_f * 2) / 2
    t0, q_len, hist = (jnp.asarray(x, jnp.int32) for x in (t0, q_len, hist))
    return jnp.asarray(sc_h), jnp.asarray(sc_f), t0, q_len, hist * (q_len > 0)


def _kept_by(thr, tie, scores, valid, t0, q_len):
    """The sets ``dsa_threshold_tpu``'s two numbers a query stand for, on
    ``_chunk_dense``'s axis (each query by its own row's pair)."""
    from helix_tpu.ops.paged import _row_of_tokens

    T = scores.shape[0]
    at = (jnp.clip(_row_of_tokens(t0, q_len, T)[0], 0), jnp.arange(T))
    return np.asarray(dsa.kept(scores, valid, thr[at], tie[at]))


def _stable_top_k(scores, valid, k):
    scores, valid = np.asarray(scores), np.asarray(valid)
    want = np.zeros(scores.shape, bool)
    for t in range(len(scores)):
        key = np.where(valid[t], scores[t], -np.inf)
        order = np.argsort(-key, kind="stable")[:min(k, valid[t].sum())]
        want[t, order] = True
    return want & valid


@pytest.mark.parametrize("shape", ["decode", "chunk"])
def test_pallas_kernels_in_interpret_mode_against_the_plain_forms(shape):
    from helix_tpu.ops.attention import DEFAULT_MASK_VALUE
    from helix_tpu.ops.dsa_kernel import (
        dsa_index_scores_tpu, dsa_threshold_tpu,
        mla_sparse_attention_tpu, mla_sparse_chunk_attention_tpu,
    )
    from helix_tpu.ops.paged import (
        dsa_index_scores_reference, mla_sparse_attention_reference,
    )

    Rq, R, T, S = (3, 3, 1, 300) if shape == "decode" else (1, 2, 40, 200)
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(k[0], (Rq, T, 8, 128))
    w = jax.random.normal(k[1], (Rq, T, 8))
    keys = jax.random.normal(k[2], (R, S, 128))
    lim = jnp.asarray([S, 70, 130][:R])
    got = dsa_index_scores_tpu(q, w, keys, lim, interpret=True)
    want = dsa_index_scores_reference(q, w, keys)
    assert got.shape == (R, T, S)
    # (past a row's ``lim`` keys the scores are unspecified)
    seen = jnp.arange(S)[None, None] < lim[:, None, None]
    assert float(jnp.abs(jnp.where(seen, got - want, 0.0)).max()) < 1e-3
    q = jax.random.normal(k[3], (Rq, T, 8, 256)) * 0.1
    kv = jax.random.normal(k[4], (R, S, 256))
    if shape == "decode":
        bias = jnp.where(jax.random.uniform(k[5], (R, T, S)) < 0.3, 0.0,
                         DEFAULT_MASK_VALUE)
        bias = bias.at[0, 0].set(DEFAULT_MASK_VALUE)   # a query keeps none
        got = mla_sparse_attention_tpu(q, kv, bias, latent=128,
                                       interpret=True)
        want = mla_sparse_attention_reference(q, kv, bias, 128)
        assert float(jnp.abs(got - want).max()) < 1e-5
        assert float(jnp.abs(got[0, 0]).max()) == 0.0
        return
    # the chunk form is handed the scores and two numbers a query, and is
    # held to the plain form under the bias ``topk_mask`` builds: rows of
    # 22 and 15 tokens behind 130 and 0 cached keys, tokens 22-24 in no row
    sc_h, sc_f, t0, q_len, lim = _chunk_case(
        R, T, S, [0, 25], [22, 15], [130, 0])
    thr, tie = dsa_threshold_tpu(sc_h, sc_f, t0, q_len, lim, topk=32,
                                 interpret=True)
    fresh = jax.random.normal(k[5], (T, 256))
    got = mla_sparse_chunk_attention_tpu(
        q[0], kv, fresh, sc_h, sc_f, thr, tie, t0, q_len, lim, latent=128,
        interpret=True)
    scores, valid, onehot = dsa._chunk_dense(sc_h, sc_f, t0, q_len, lim)
    chosen = dsa.topk_mask(scores, valid, 32)
    assert int(chosen.sum(-1).max()) == 32 and int(chosen[25].sum()) == 1
    bias = jnp.where(chosen[None] & onehot[:, :, None], 0.0,
                     DEFAULT_MASK_VALUE)
    want = mla_sparse_attention_reference(
        q, jnp.concatenate(
            [kv, jnp.broadcast_to(fresh[None], (R, T, 256))], axis=1),
        bias, 128)
    assert float(jnp.abs(got - want).max()) < 1e-5
    # a token outside every row keeps nothing: zeros, in both rows
    assert float(jnp.abs(got[:, 22:25]).max()) == 0.0
    assert float(jnp.abs(got[1, :22]).max()) == 0.0


def test_the_threshold_by_bisection_is_the_stable_top_k():
    """``topk_mask`` (32 passes of compare-and-count over the float's bits)
    chooses what a stable sort chooses: ties to the smaller index, negative
    scores and -0.0 in their order, a row with fewer valid entries than
    ``k`` whole."""
    rng = np.random.default_rng(0)
    sc = rng.normal(size=(6, 90)).astype(np.float32)
    sc[1] = np.round(sc[1] * 2) / 2            # many ties, at the threshold
    sc[2, :] = 0.0                             # all tied
    sc[3, ::2] = -0.0
    valid = np.ones_like(sc, bool)
    valid[4, 20:] = False                      # fewer than k valid
    valid[5, ::3] = False
    got = np.asarray(dsa.topk_mask(jnp.asarray(sc), jnp.asarray(valid), 32))
    np.testing.assert_array_equal(got, _stable_top_k(sc, valid, 32))


# what the threshold kernel is held to: (rows' t0, q_len, hist) on a flat
# axis of 24 queries behind tables 384 wide, and what the scores hold
THRESHOLD_CASES = {
    "ties_at_the_threshold": ([0], [24], [300], "halves"),
    "all_tied": ([0], [24], [300], "zeros"),
    "negative_zero": ([0], [24], [300], "negative_zero"),
    "fewer_valid_than_k": ([0], [24], [5], "halves"),
    "two_rows_in_one_flat_axis": ([0, 9], [9, 13], [140, 290], "halves"),
    "a_row_with_no_history": ([0, 12], [10, 12], [0, 200], "halves"),
    "no_two_scores_alike": ([0], [24], [300], "normal"),
}


@pytest.mark.parametrize("case", list(THRESHOLD_CASES))
def test_the_threshold_kernel_chooses_what_the_stable_sort_chooses(
        case, monkeypatch):
    """``dsa_threshold_tpu`` in interpret mode: its two numbers a query
    stand for ``topk_mask``'s set, which is the stable sort's; key blocks of
    128, and NaN in every score block past a row's ``lim`` (a pass that read
    them would poison the count)."""
    from helix_tpu.ops import dsa_kernel

    monkeypatch.setattr(dsa_kernel, "SCORE_KEY_BLOCK", 128)
    t0, q_len, hist, fill = THRESHOLD_CASES[case]
    R, T, S = len(t0), 24, 384
    sc_h, sc_f, t0, q_len, lim = _chunk_case(
        R, T, S, t0, q_len, hist, ties=fill != "normal")
    if fill == "zeros":
        sc_h, sc_f = jnp.zeros_like(sc_h), jnp.zeros_like(sc_f)
    elif fill == "negative_zero":
        sc_h, sc_f = sc_h.at[..., ::2].set(-0.0), sc_f.at[..., ::2].set(-0.0)
    dead = jnp.arange(S)[None] >= (-(-lim // 128) * 128)[:, None]
    thr, tie = dsa_kernel.dsa_threshold_tpu.__wrapped__(
        jnp.where(dead[:, None], jnp.nan, sc_h), sc_f, t0, q_len, lim,
        topk=32, interpret=True)
    scores, valid, _ = dsa._chunk_dense(sc_h, sc_f, t0, q_len, lim)
    got = _kept_by(thr, tie, scores, valid, t0, q_len)
    np.testing.assert_array_equal(
        got, np.asarray(dsa.topk_mask(scores, valid, 32)))
    np.testing.assert_array_equal(got, _stable_top_k(scores, valid, 32))
    # a query of no more than k keys keeps them all under threshold 1; a
    # query outside a row keeps nothing of it
    n = np.asarray(valid.sum(-1))
    row = np.asarray(dsa._row_of_tokens(t0, q_len, T)[0])
    for t in range(T):
        if row[t] >= 0 and n[t] <= 32:
            assert int(thr[row[t], t]) == 1 and got[t].sum() == n[t]
        for r in range(R):
            if r != row[t]:
                assert int(thr[r, t]) == 0xFFFFFFFF and int(tie[r, t]) == -1


def _interpreted(monkeypatch):
    """``ops/dsa.py``'s Pallas path on the CPU: its kernels in interpret
    mode."""
    import functools

    from helix_tpu.ops import dsa_kernel

    for name in ("dsa_index_scores_tpu", "dsa_threshold_tpu",
                 "mla_sparse_attention_tpu",
                 "mla_sparse_chunk_attention_tpu"):
        monkeypatch.setattr(dsa_kernel, name, functools.partial(
            getattr(dsa_kernel, name), interpret=True))


def _pool_case(T, hist, q_len, t0, pages, kinds=4, seed=0):
    """``dsa_ragged_paged_attention``'s arguments for rows on one flat axis
    of ``T`` tokens: 8 heads over a latent of 128 + 64 rope lanes, 8 index
    heads of 128, pages of 16.  Index keys are drawn from ``kinds``
    vectors, and they, the index queries and the heads' weights hold small
    whole numbers: every score is exact in float32 whatever the order of
    its sums, so both backends read the SAME scores and many are EXACTLY
    tied."""
    R = len(hist)
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    N = R * pages + 1
    some = jax.random.randint(k[0], (kinds, 128), -2, 3).astype(jnp.float32)
    idx_pages = some[jax.random.randint(k[1], (1, N, 16), 0, kinds)]
    i_new = some[jax.random.randint(k[2], (T,), 0, kinds)]
    kv_pages = jax.random.normal(k[3], (1, N, 16, 256))
    tables = 1 + jnp.arange(R * pages, dtype=jnp.int32).reshape(R, pages)
    return dict(
        q=jax.random.normal(k[4], (T, 8, 192)) * 0.1,
        c_new=jax.random.normal(k[5], (T, 128)),
        r_new=jnp.concatenate(
            [jax.random.normal(k[6], (T, 64)), i_new], axis=-1),
        qi=jax.random.randint(k[7], (T, 8 * 128 + 8), -1, 3).astype(
            jnp.float32),
        kv_pages=kv_pages, idx_pages=idx_pages, layer=0,
        t0=jnp.asarray(t0, jnp.int32), q_len=jnp.asarray(q_len, jnp.int32),
        hist=jnp.asarray(hist, jnp.int32), tables=tables)


def test_the_chunk_branch_on_the_kernels_is_the_reference_backends(
        monkeypatch):
    """``dsa_ragged_paged_attention``'s chunk branch, Pallas in interpret
    mode against ``backend='reference'``: a row whose history is shorter
    than ``topk`` and one whose history is longer, exact ties at the
    threshold (four kinds of index key), and what the probe is shown is the
    reference backend's scores and sets."""
    _interpreted(monkeypatch)
    a = _pool_case(T=40, hist=[20, 150], q_len=[14, 24], t0=[0, 16], pages=12)
    seen = {}
    outs = {}
    for backend in ("pallas", "reference"):
        monkeypatch.setattr(
            dsa, "PROBE", lambda kind, *x, b=backend: seen.update({b: x}))
        outs[backend] = dsa.dsa_ragged_paged_attention(
            **a, index_heads=8, topk=32, backend=backend, max_q_len=40)
        jax.effects_barrier()
    assert float(jnp.abs(outs["pallas"] - outs["reference"]).max()) < 1e-5
    assert float(jnp.abs(outs["pallas"][14:16]).max()) == 0.0
    scores, chosen = seen["reference"][-2:]
    ours, ours_chosen = seen["pallas"][-2:]
    counted = np.asarray(dsa._chunk_dense(
        jnp.zeros((2, 40, 192)), jnp.zeros((40, 40)), a["t0"], a["q_len"],
        a["hist"])[1])
    np.testing.assert_array_equal(ours_chosen, chosen)
    np.testing.assert_array_equal(np.where(counted, ours, 0.0),
                                  np.where(counted, scores, 0.0))
    # the threshold was tied, and cut by position: a query of the long row
    # has more keys AT its 32nd score than it keeps of them
    t = 30
    kth = np.sort(scores[t][counted[t]])[-32]
    at = counted[t] & (scores[t] == kth)
    assert chosen[t].sum() == 32 and at.sum() > (at & chosen[t]).sum() > 0


def test_a_chunks_choice_writes_nothing_the_width_of_the_table_but_scores():
    """THE MECHANISM, pinned: in the chunk branch's program on the Pallas
    backend, at 512 queries behind a table 4,096 wide, no equation outside
    the kernels writes an array of queries x table width (512 x 4,096
    elements or more: the gathered rows, 4,096 x 256, are under it) but
    the scoring kernel's float32 scores: no ``valid``, no ordered bits, no
    ``chosen``, no bias."""
    T, S = 512, 4096
    a = _pool_case(T=T, hist=[3000], q_len=[T], t0=[0], pages=S // 16)
    jaxpr = jax.make_jaxpr(lambda kw: dsa.dsa_ragged_paged_attention(
        **kw, layer=0, index_heads=8, topk=2048, backend="pallas",
        max_q_len=T))({k: v for k, v in a.items() if k != "layer"})
    kernels, wide = [], []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                kernels.append(eqn)
                continue
            if eqn.params.get("name") == "dsa_index_scores_tpu":
                continue                        # the scores themselves
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
            wide.extend((eqn.primitive.name, v.aval.shape)
                        for v in eqn.outvars
                        if hasattr(v.aval, "shape")
                        and np.prod(v.aval.shape) >= T * S)

    walk(jaxpr.jaxpr)
    assert len(kernels) == 2 and not wide, wide


def test_the_kernels_skip_the_key_blocks_no_query_can_see(monkeypatch):
    """Key blocks of 128 (patched down from 512 so that a small case has
    several): a row's history is 384 gathered positions of which ``lim`` are
    real, 24 fresh tokens behind them.  Dead blocks hold NaN: a block that
    was fetched and multiplied would poison the output."""
    from helix_tpu.ops import dsa_kernel
    from helix_tpu.ops.attention import DEFAULT_MASK_VALUE
    from helix_tpu.ops.paged import (
        dsa_index_scores_reference, mla_sparse_attention_reference,
    )

    monkeypatch.setattr(dsa_kernel, "ATTN_KEY_BLOCK", 128)
    monkeypatch.setattr(dsa_kernel, "SCORE_KEY_BLOCK", 128)
    R, T, S = 2, 24, 384
    sc_h, sc_f, t0, q_len, lim = _chunk_case(
        R, T, S, [0, 9], [9, 15], [130, 0])
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    live = jnp.arange(S)[None] < (-(-lim // 128) * 128)[:, None]
    q = jax.random.normal(k[0], (1, T, 8, 128))
    w = jax.random.normal(k[1], (1, T, 8))
    keys = jnp.where(live[..., None], jax.random.normal(k[2], (R, S, 128)),
                     jnp.nan)
    got = dsa_kernel.dsa_index_scores_tpu.__wrapped__(
        q, w, keys, lim, interpret=True)
    want = dsa_index_scores_reference(q, w, keys)
    seen = jnp.arange(S)[None, None] < lim[:, None, None]
    assert float(jnp.abs(jnp.where(seen, got - want, 0.0)).max()) < 1e-3
    q = jax.random.normal(k[3], (T, 8, 256)) * 0.1
    kv = jnp.where(live[..., None], jax.random.normal(k[4], (R, S, 256)),
                   jnp.nan)
    fresh = jax.random.normal(k[5], (T, 256))
    dead_scores = jnp.where(live[:, None], sc_h, jnp.nan)
    thr, tie = dsa_kernel.dsa_threshold_tpu.__wrapped__(
        dead_scores, sc_f, t0, q_len, lim, topk=32, interpret=True)
    got = dsa_kernel.mla_sparse_chunk_attention_tpu.__wrapped__(
        q, kv, fresh, dead_scores, sc_f, thr, tie, t0, q_len, lim,
        latent=128, interpret=True)
    scores, valid, onehot = dsa._chunk_dense(sc_h, sc_f, t0, q_len, lim)
    bias = jnp.where(dsa.topk_mask(scores, valid, 32)[None]
                     & onehot[:, :, None], 0.0, DEFAULT_MASK_VALUE)
    want = mla_sparse_attention_reference(
        q[None], jnp.concatenate(
            [jnp.nan_to_num(kv), jnp.broadcast_to(fresh[None], (R, T, 256))],
            axis=1), bias, 128)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < 1e-5


# ---- held experts -----------------------------------------------------------


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST of this family (the other held-experts families have
    theirs): one expert layer, 32 routed experts, SIXTEEN ranks of two: each
    rank routes over all 32 at top-3 behind the sigmoid router with its
    selection bias and computes its own experts' part; the parts add up to
    what a chip that held every expert computes, and with the shared expert
    ONCE to the reference's uncut layer.  float32, a sum in another order."""
    whole_cfg = tiny(num_experts=32)
    E, X, F, T, share = 64, 32, 32, 50, 2
    ks = jax.random.split(jax.random.PRNGKey(4), 8)
    x = jax.random.normal(ks[0], (1, T, E))
    w_r = jax.random.normal(ks[1], (E, X)) * 0.3
    bias = jax.random.normal(ks[2], (X,)) * 0.03
    experts = {n: {"weight": jax.random.normal(k, shp) * 0.05}
               for n, k, shp in (("w_gate", ks[3], (X, E, F)),
                                 ("w_up", ks[4], (X, E, F)),
                                 ("w_down", ks[5], (X, F, E)))}
    shared = {n: {"weight": jax.random.normal(k, shp)[None] * 0.05}
              for n, k, shp in (("w_gate", ks[6], (E, F)),
                                ("w_up", ks[7], (E, F)),
                                ("w_down", ks[6], (F, E)))}
    hf = hf_of(whole_cfg)
    lp = {"router": {"weight": w_r[None]},
          "expert_bias": {"bias": bias[None]},
          "experts": jax.tree.map(lambda a: a[None], experts),
          "shared": shared}
    with jax.default_matmul_precision("highest"):
        whole = moe_ffn(x, w_r, experts, whole_cfg, jax.nn.silu,
                        expert_bias=bias, backend="reference")
        parts, shares = [], []
        for lo in range(0, X, share):
            cfg = dataclasses.replace(whole_cfg,
                                      held_experts=(lo, lo + share))
            mine = jax.tree.map(lambda a: a[lo:lo + share], experts)
            parts.append(moe_ffn(x, w_r, mine, cfg, jax.nn.silu,
                                 expert_bias=bias, backend="reference"))
            shares.append(reference.expert_layer(
                x[0], dict(lp, experts=jax.tree.map(
                    lambda a: a[:, lo:lo + share], lp["experts"])), 0,
                dict(hf, held_experts=[lo, lo + share]), {"shared": False}))
        uncut = reference.expert_layer(
            x[0], lp, 0, dict(hf, held_experts=None), {})
        once = reference.glu(x[0], shared, 0, 0.0)
    assert len(parts) == 16
    assert float(jnp.abs(sum(parts) - whole).max()) < 1e-5
    assert float(jnp.abs(sum(parts)[0] + once - uncut).max()) < 1e-5
    assert float(jnp.abs(sum(shares) + once - uncut).max()) < 1e-5
    assert float(jnp.abs(uncut).max()) > 1e-2
    assert all(float(jnp.abs(p - whole).max()) > 1e-3 for p in parts)


# ---- the configuration, the pools, the refusals ------------------------------


GLM5_ROW = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "head_dim": 64, "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
    "indexer_rope_interleave": True, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 202752,
    "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "model_type": "glm_moe_dsa", "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 78, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_interleave": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 256, "vocab_size": 154880,
}


def test_catalog_entry_is_the_published_config():
    got = ModelConfig.from_hf_config(GLM5_ROW, name="zai-org/GLM-5")
    assert got == CATALOG["zai-org/GLM-5"]
    assert got.is_dsa and got.is_mla and got.kv_token_shapes() == (
        (512,), (64 + 128,))
    assert (got.index_heads, got.index_head_dim, got.index_topk) == (
        32, 128, 2048)
    assert got.rope_theta == 1e6 and got.rope_scaling is None
    assert got.moe_scoring == "sigmoid" and got.moe_expert_bias
    assert got.first_k_dense == 3 and got.num_experts == 256
    # 78 layers + embedding and head: the published size
    L, E = 78, 6144
    attn = (E * 2048 + 2048 * 64 * 256 + E * 576 + 512 * 64 * 448
            + 64 * 256 * E)
    index = 2048 * 32 * 128 + E * 128 + E * 32
    expert = 3 * E * 2048
    total = (L * (attn + index) + 3 * 3 * E * 12288
             + 75 * (257 * expert + E * 256) + 2 * 154880 * E)
    assert attn == 165_019_648 and index == 9_371_648
    assert 743e9 < total < 745e9
    cut = ModelConfig.from_hf_config(dict(
        GLM5_ROW, num_hidden_layers=8, first_k_dense_replace=1,
        n_routed_experts=16, published_n_routed_experts=256,
        held_experts=[0, 16]))
    assert cut.held_experts == (0, 16) and cut.num_experts == 256
    assert cut.num_held_experts == 16


@pytest.mark.parametrize("bad,match", [
    (dict(n_group=2), "n_group 1"),
    (dict(topk_group=4), "n_group 1"),
    (dict(moe_layer_freq=2), "expert layer at every layer"),
    (dict(topk_method="group_limited_greedy"), "greedy router"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
     "rope_type 'yarn' is not supported"),
    (dict(index_topk=None), r"the indexer's \['index_topk'\] are not given"),
    (dict(index_n_heads=0, index_head_dim=0),
     "promises a sparse-attention indexer"),
    (dict(rope_interleave=False), "only rope_interleave true"),
    (dict(indexer_rope_interleave=False),
     "only indexer_rope_interleave true"),
    (dict(n_routed_experts=16, published_n_routed_experts=256,
          held_experts=[0, 8]),
     r"glm_moe_dsa: held_experts \[0, 8\] are not the 16 of "
     "n_routed_experts"),
])
def test_what_from_hf_config_does_not_serve_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(dict(GLM5_ROW, **bad))


@pytest.mark.parametrize("family,hf,match", [
    ("gigachat3_5", dict(n_routed_experts=4), "gigachat3_5: held_experts "
     r"\[0, 8\] are not the 4 of n_routed_experts"),
    ("laguna", dict(num_experts=4), r"laguna: held_experts \[0, 8\] are "
     "not the 4 of num_experts"),
    ("nemotron_h", dict(n_routed_experts=4), "nemotron_h: held_experts "
     r"\[0, 8\] are not the 4 of n_routed_experts"),
])
def test_one_helper_parses_held_experts_for_every_family(family, hf, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig._held_experts(
            dict(hf, held_experts=[0, 8]), family,
            "num_experts" if family == "laguna" else "n_routed_experts")
    assert ModelConfig._held_experts({}, family) == {}
    assert ModelConfig._held_experts(
        dict(n_routed_experts=8, published_n_routed_experts=64,
             held_experts=[8, 16]), family) == dict(
                 held_experts=(8, 16), num_experts=64)


def test_both_pools_count_what_they_allocate_and_write_kv_scatters_both():
    cfg = tiny()
    cc = CacheConfig(num_pages=10, page_size=8, dtype="float32")
    assert cc.page_shapes(cfg) == ((3, 8, 32 + 128), (3, 8, 16))
    assert cc.page_bytes(cfg) == 3 * 8 * (32 + 128 + 16) * 4
    assert cc.geometry(cfg) == (0, 32 + 128 + 16)
    assert cc.total_bytes(cfg) == 10 * cc.page_bytes(cfg)
    assert CacheConfig.fit_hbm(cfg, 10 * cc.page_bytes(cfg) + 5, page_size=8,
                               dtype="float32").num_pages == 10
    full = CacheConfig(num_pages=1, page_size=16, dtype="bfloat16")
    glm = dataclasses.replace(CATALOG["zai-org/GLM-5"], num_layers=8,
                              first_k_dense=1)
    # 1,280 B of latent row + 256 B of index key a token and layer
    assert full.page_bytes(glm) == 8 * 16 * (1280 + 256) == 196_608
    cache = PagedKVCache.create(cfg, cc)
    assert cache.latent and cache.k_pages.shape == (3, 10, 8, 160)
    assert cache.v_pages.shape == (3, 10, 8, 16)
    assert len(jax.tree.leaves(cache)) == 2
    c = jnp.arange(3 * 4 * 32, dtype=jnp.float32).reshape(3, 1, 4, 32)
    r = jnp.concatenate([jnp.ones((3, 1, 4, 8)),
                         2.0 + jnp.arange(3 * 4 * 16, dtype=jnp.float32
                                          ).reshape(3, 1, 4, 16)], -1)
    cache = write_kv(cache, c, r, jnp.asarray([[2, 2, 5, 0]]),
                     jnp.asarray([[6, 7, 0, 0]]),
                     jnp.asarray([[True, True, True, False]]))
    np.testing.assert_array_equal(cache.k_pages[:, 2, 6, :32], c[:, 0, 0])
    assert float(cache.k_pages[0, 2, 7, 32:40].sum()) == 8   # the rope key
    assert float(cache.k_pages[..., 40:].sum()) == 0      # the lane padding
    np.testing.assert_array_equal(cache.v_pages[:, 2, 6], r[:, 0, 0, 8:])
    np.testing.assert_array_equal(cache.v_pages[:, 5, 0], r[:, 0, 2, 8:])
    # (a padding token lands in the garbage page 0 of both pools)
    assert float(jnp.abs(cache.v_pages[:, 1]).sum()) == 0


REFUSED = {
    "int8 kv": (dict(kv_cache_dtype="int8"), "latent attention"),
    "adapters": (dict(adapter_pool_slots=2), "latent attention"),
    "speculation": (dict(enable_spec_decode=True), "latent attention"),
    "tiered residency": (dict(ctx_hot_pages=4, host_pool_bytes=1 << 20),
                         "latent attention"),
    "the host tier": (dict(host_pool_bytes=1 << 20),
                      "an index-key pool beside the latent pool"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_what_the_index_pool_is_not_served_with_is_refused(what):
    cfg = UNPROBED
    params = init_params(cfg, jax.random.PRNGKey(0))
    kw, match = REFUSED[what]
    with pytest.raises(UnsupportedForModel, match=match):
        _engine(cfg, params, **kw)


@pytest.mark.parametrize("call", ["export_request", "export_prefill",
                                  "import_request", "filestore"])
def test_paths_that_move_a_pages_contents_are_refused_by_call(call):
    cfg = UNPROBED
    eng = _engine(cfg, init_params(cfg, jax.random.PRNGKey(0)))
    with pytest.raises(UnsupportedForModel,
                       match="an index-key pool beside the latent pool"):
        if call == "filestore":
            eng.kv_filestore = object()
        elif call == "import_request":
            eng.import_request({})
        else:
            getattr(eng, call)("nobody")


def test_a_prefix_cache_hit_that_shares_pages_serves_the_index_keys_too(
        model, probe):
    """The prefix cache shares page IDS and never looks inside a page: a page
    of the latent pool is the same page of the index-key pool.  The same 60-
    token prompt twice: the second run's remainder scores the FIRST run's
    cached index keys (its history is the shared pages), chooses past 32
    keys, and returns what the cold run returned."""
    cfg, params = model[0], init_params(model[0], jax.random.PRNGKey(5))
    eng = _engine(cfg, params, enable_prefix_cache=True)
    prompt = tokens_of(60, seed=4)
    sp = SamplingParams(max_tokens=5, temperature=0.0)
    first = eng.generate([prompt], sp)[0]
    hits = eng.prefix_cache.stats["hits"]
    cold = dict(probe.scores)
    probe.scores.clear()
    second = eng.generate([prompt], sp)[0]
    assert eng.prefix_cache.stats["hits"] > hits
    assert first == second
    # the hit's queries scored the shared pages' index keys: the same scores
    # the cold run computed at those positions
    again = {at: sc for at, sc in probe.scores.items() if at[2] >= 48}
    assert again
    for (f, l, p), sc in again.items():
        match = [v for (_, l2, p2), v in cold.items() if (l2, p2) == (l, p)]
        assert match and np.abs(match[0] - sc).max() < SCORE_TOL * np.sqrt(
            np.mean(sc ** 2))


def test_the_hosts_account_is_exported_and_read():
    """``helix_dsa_*`` on ``/metrics``, the launch span's attributes and the
    flight record's fields, by hand for a 40-token prompt in chunks of 16 and
    three decode steps at ``index_topk`` 32."""
    from helix_tpu.obs import trace as obs_trace
    from helix_tpu.serving.engine_loop import EngineLoop
    from helix_tpu.serving.openai_api import OpenAIServer
    from helix_tpu.serving.registry import ModelRegistry, ServedModel
    from helix_tpu.serving.tokenizer import ByteTokenizer

    cfg = UNPROBED
    eng = _engine(cfg, init_params(cfg, jax.random.PRNGKey(3)))
    seen = []
    orig = obs_trace.phase

    def phase(name, *a, **kw):
        if name == "helix.loop.launch":
            seen.append({k: v for k, v in kw.items() if k.startswith("dsa_")})
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
    L, K = cfg.num_attn_layers, TOPK
    # chunks at 0 (cold: the latent kernel, nothing scored), 16 and 32 tokens
    # of history, then one-token rows with 41, 42, 43 keys
    def chunk(start, rem):
        n = np.arange(start + 1, start + rem + 1)
        return int(n.sum()), int(np.minimum(n, K).sum())

    scored = chunk(16, 16)[0] + chunk(32, 8)[0] + 41 + 42 + 43
    chosen = chunk(16, 16)[1] + chunk(32, 8)[1] + 3 * K
    c = eng.mixer_counts
    assert c["keys_scored"] == scored * L and c["keys_selected"] == chosen * L
    assert (c["rows_chunk_all"], c["rows_chunk_sparse"]) == (2, 1)
    assert (c["rows_decode_all"], c["rows_decode_sparse"]) == (0, 3)
    # a decode row past 32 keys fetches 32 latent rows a layer, not its
    # history; a chunk row's history is fetched once
    assert c["latent_rows_fetched"] == (32 + 40 + 3 * K) * L
    # what the gather moves out of the index-key pool: both slots' tables
    # at their whole width (16 pages of 16 float32 keys of 16) in each of
    # the six launches, and the chunk's one row in the two that choose
    assert len(seen) == 6
    assert c["index_bytes_read"] == (6 * 2 + 2) * 16 * 16 * 16 * 4 * L
    assert seen[0]["dsa_keys_scored"] == 0
    assert seen[-1]["dsa_latent_rows_fetched"] == K * L
    # what a chunk row's choice moves: the float32 scores of the bucket's 16
    # queries over the row's live key blocks (one, of the table's 256
    # positions) and the 16 fresh tokens, written once and read twice; none
    # for the cold first chunk (nothing chosen) and none for a decode row
    select = 3 * 4 * 16 * (256 + 16) * L
    assert [s["dsa_select_bytes"] for s in seen] == [0, select, select,
                                                     0, 0, 0]
    assert c["select_bytes"] == 2 * select
    assert sum(s["dsa_keys_scored"] for s in seen) == c["keys_scored"]
    registry = ModelRegistry()
    loop = EngineLoop(eng, "tiny-dsa")
    registry.register(ServedModel(
        name="tiny-dsa", loop=loop, tokenizer=ByteTokenizer(),
        context_length=128))
    text = OpenAIServer(registry).obs.render()

    def series(name, **labels):
        for ln in text.splitlines():
            if ln.startswith(name + "{") and all(
                    f'{k}="{v}"' in ln for k, v in labels.items()):
                return float(ln.rsplit(" ", 1)[1])
        raise AssertionError(name)

    assert series("helix_dsa_keys_scored_total") == scored * L
    assert series("helix_dsa_keys_selected_total") == chosen * L
    assert series("helix_dsa_rows_total", kind="decode", mode="sparse") == 3
    assert series("helix_dsa_rows_total", kind="chunk", mode="all") == 2
    assert series("helix_dsa_latent_rows_fetched_total") == c[
        "latent_rows_fetched"]
    assert series("helix_dsa_index_bytes_read_total") == c["index_bytes_read"]
    assert series("helix_dsa_select_bytes_total") == 2 * select
    assert series("helix_dsa_index_pool_bytes") == 64 * L * 16 * 16 * 4
    assert "helix_mla_page_fetches_total" in text
    loop._flight_record(0.0, loop._flight_pre(), 0)
    rec = loop.flight.snapshot()["recent"][-1]
    assert rec["dsa_keys_scored"] == 0 and "dsa_latent_rows_fetched" in rec
    assert rec["dsa_select_bytes"] == 0


def test_int8_tree_and_logical_axes_cover_the_indexers_tensors():
    from helix_tpu.ops.quant import quantize_params, quantized_logical_axes

    cfg = tiny()
    # (shapes, dtypes and structure are all that is asked: nothing is run)
    born = jax.eval_shape(
        lambda k: init_params(cfg, k, int8=True), jax.random.PRNGKey(0))
    made = jax.eval_shape(
        lambda k: quantize_params(init_params(cfg, k)), jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)  # noqa
    assert shapes(born) == shapes(made)
    for stack in ("dense_layers", "layers"):
        for name in ("wq_idx", "wk_idx", "w_idx", "k_idx_norm"):
            assert name in born[stack], (stack, name)
        assert born[stack]["wq_idx"]["weight"].dtype == jnp.int8
        assert born[stack]["k_idx_norm"]["bias"].shape[-1] == 16
    axes = quantized_logical_axes(param_logical_axes(cfg))
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=is_axes)
    ) == jax.tree.structure(jax.tree.map(lambda a: 0, born))
    jax.tree.map(lambda ax, leaf: None if len(ax) == leaf.ndim else 1 / 0,
                 axes, born, is_leaf=is_axes)
