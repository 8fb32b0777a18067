"""GigaChat3.5-style hybrid decoders on the CPU at a small size, float32,
seeded weights: gated delta-rule layers (a conv tail and a matrix state a
slot) beside latent-attention layers with a compressed, gated query (latent
pages), sandwich norms, a clamped SwiGLU, and routed experts of which the
chip holds one expert-parallel rank's.  The oracle is the benchmark's plain
reference (``benchmark/lib/reference_deltanet_mla_moe_decoder.py``: the
token-by-token recurrence, unabsorbed attention, a loop over experts); the
engine is compared by LOGITS."""

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

from benchmark.lib import (  # noqa: E402
    reference_deltanet_mla_moe_decoder as reference,
)
from helix_tpu.engine.engine import (  # noqa: E402
    Engine, EngineConfig, Request, SamplingParams, UnsupportedForModel,
)
from helix_tpu.engine.kv_cache import CacheConfig, PagedKVCache  # noqa: E402
from helix_tpu.models.common import (  # noqa: E402
    CATALOG, GIGACHAT35_432B, ModelConfig,
)
from helix_tpu.models.llama import (  # noqa: E402
    forward, init_params, param_logical_axes, prefill_attn_fn,
)
from helix_tpu.models.moe import moe_ffn  # noqa: E402
from helix_tpu.ops import deltanet as D  # noqa: E402
from helix_tpu.ops.deltanet_kernel import (  # noqa: E402
    check_deltanet_geometry, deltanet_chunk_tpu,
)
from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry  # noqa: E402

HF = dict(
    model_type="gigachat3_5", vocab_size=256, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=9,
    num_attention_heads=4, num_key_value_heads=4, n_shared_experts=1,
    n_routed_experts=4, published_n_routed_experts=16, held_experts=[0, 4],
    routed_scaling_factor=2.5, kv_lora_rank=32, q_lora_rank=24,
    qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16, n_group=1,
    topk_group=1, num_experts_per_tok=4, first_k_dense_replace=1,
    norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=100000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
                  "mscale_all_dim": 1, "type": "yarn",
                  "original_max_position_embeddings": 64},
    layernorm_type="pre_post", gated_attention=True,
    use_mla_scaling_factor=True, full_attention_layers=[1, 5],
    linear_key_head_dim=16, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_sigmoid_gate_scale=2,
    linear_attn_o_norm_eps=1e-6, swiglu_limit=10, tie_word_embeddings=False,
    max_position_embeddings=512, hidden_act="silu",
)
# float32, the same mathematics through another order of operations (a state
# carried through 64-token chunks and single steps against a token-by-token
# scan; attention absorbed over a latent cache against explicit K and V; a
# sorted grouped product against a loop over experts): measured 1e-6 and
# under on logits of spread 0.16
TOL = 1e-5
# the least any fault reads at this size is the bf16 state's 6e-3 (relative
# RMS; every other fault 0.15 and over); a hundred times the engine's own
# error lies under it
FAULT_LIMIT = 3e-3


def tiny(**kw):
    cfg = ModelConfig.from_hf_config(dict(HF, **kw), name="tiny-gigachat")
    return dataclasses.replace(cfg, dtype="float32")


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = init_params(cfg, jax.random.PRNGKey(1))
    # norm offsets off zero, so that a gain read as ``w`` and not ``1 + w``
    # is seen
    k = jax.random.PRNGKey(2)
    for key in ("run00", "run01", "run02"):
        for name in ("attn_norm", "attn_post_norm", "mlp_norm",
                     "mlp_post_norm"):
            k, sub = jax.random.split(k)
            w = params[key][name]["weight"]
            params[key][name]["weight"] = w + 0.1 * jax.random.normal(
                sub, w.shape)
    return cfg, params


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


def _draw(n, H=4, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (D.l2norm(jax.random.normal(ks[0], (n, H, d))) * d ** -0.5,
            D.l2norm(jax.random.normal(ks[1], (n, H, d))),
            jax.random.normal(ks[2], (n, H, d)),
            -jnp.exp(jax.random.normal(ks[3], (n, H)) - 2.0),
            jax.nn.sigmoid(jax.random.normal(ks[4], (n, H))))


# ---- the delta rule: recurrence, chunked form, kernel ----------------------


@pytest.mark.parametrize("T", [1, 63, 64, 65, 150, 512, 1400])
@pytest.mark.parametrize("from_state", [False, True])
def test_chunked_form_is_the_recurrence(T, from_state):
    """Across a chunk boundary (65, 150, 1400), at it (64, 512), inside it
    (1, 63), from zeros and from a state that is not zero: outputs and the
    state left.  float32 at the highest precision both sides: 1e-5 of
    outputs of size 0.5 (measured 2e-6 at 1,400 tokens)."""
    args = _draw(T, seed=T)
    S0 = jnp.zeros((4, 16, 16))
    if from_state:
        S0 = 0.3 * jax.random.normal(jax.random.PRNGKey(9), S0.shape)
    with jax.default_matmul_precision("highest"):
        want, S_want = D.delta_recurrence(*args, S0)
        got, S_got = D.delta_sequence(*args, S0)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(S_got - S_want).max()) < 1e-5
    assert float(jnp.abs(want).max()) > 1e-2


def test_a_bf16_state_fails_the_chunked_forms_tolerance():
    """The control in lower precision: the recurrence with its state rounded
    to bfloat16 after every token parts from the float32 one by ten times
    (measured: 88 times) the 1e-5 the chunked form is held to."""
    args = _draw(150, seed=150)
    S0 = jnp.zeros((4, 16, 16))

    def rounded(S, x):
        o, S = D.delta_step(*(a[None] for a in x), S[None])
        return S[0].astype(jnp.bfloat16).astype(jnp.float32), o[0]

    with jax.default_matmul_precision("highest"):
        want, _ = D.delta_recurrence(*args, S0)
        _, got = jax.lax.scan(rounded, S0, args)
    assert float(jnp.abs(got - want).max()) > 1e-4


@pytest.mark.parametrize("C", [1, 2, 8, 64])
def test_the_block_recursion_inverts_a_unit_lower_triangle(C):
    """``unit_lower_inverse`` against ``numpy.linalg.inv`` over a batch, and
    on the system that breaks a Neumann series in float32: every entry under
    the diagonal 1 (identical keys written at full strength), whose inverse
    is the diagonal's ones over a subdiagonal of -1."""
    M = np.tril(np.random.RandomState(C).randn(3, 5, C, C), -1).astype(
        np.float32)
    M[0, 0] = np.tril(np.ones((C, C), np.float32), -1)
    got = np.asarray(D.unit_lower_inverse(jnp.asarray(M)))
    want = np.linalg.inv(M.astype(np.float64) + np.eye(C))
    assert np.abs(got - want).max() < 1e-4 * max(1.0, np.abs(want).max())
    assert np.array_equal(got[0, 0], np.eye(C) - np.eye(C, k=-1))


def test_chunk_table_by_hand():
    """Rows of 70, 0, 130 and 1 tokens, the third with no slot: the slotted
    rows' chunks first and in order, then the other's, then inert entries."""
    big = np.iinfo(np.int32).max
    tab, count = D.chunk_table(
        jnp.array([0, 70, 70, 200]), jnp.array([70, 0, 130, 1]),
        jnp.array([5, 9, 0, 0]), jnp.array([2, 1, big, 0]), 8, 4)
    tab = {k: np.asarray(v).tolist() for k, v in tab.items()}
    assert int(count) == 6
    assert tab["start"][:6] == [0, 64, 200, 70, 134, 198]
    assert tab["left"] == [70, 6, 1, 130, 66, 2, 0, 0]
    assert tab["first"] == [1, 0, 1, 1, 0, 0, 0, 0]
    assert tab["write"] == [0, 1, 1, 0, 0, 0, 0, 0]
    assert tab["slot"][:6] == [2, 2, 0, 3, 3, 3]
    assert tab["has_slot"] == [1, 1, 1, 0, 0, 0, 0, 0]
    assert tab["from_state"][:6] == [1, 1, 0, 0, 0, 0]


_BIG = np.iinfo(np.int32).max
# (tokens on the axis, t0, qlen, hist, slots): rows in PrefillPlan's order
_ROWS = {
    # unaligned starts, a row of no tokens, a slot that held something else
    "unaligned": (150, [0, 70, 0], [70, 80, 0], [5, 0, 0], [2, 0, 4]),
    # more chunks than a pass holds: rows that straddle passes, one of them
    # without a slot (its state rides from pass to pass and goes nowhere)
    "passes": (1300, [1, 700, 1290], [699, 590, 10], [0, 0, 1],
               [_BIG, 2, 4]),
    "no_slot_first": (300, [0, 150, 299, 0], [150, 149, 1, 0], [3, 0, 2, 0],
                      [3, _BIG, 0, 1]),
    "none_has_a_slot": (200, [0, 70], [70, 130], [0, 0], [_BIG, _BIG]),
    "nothing": (200, [0, 70], [0, 0], [4, 0], [1, 2]),
}


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("rows", sorted(_ROWS))
def test_rows_on_one_axis_are_each_the_recurrence(rows, backend):
    """``delta_rows`` in both forms of its second half (the scan, and the
    chunk kernel in interpret mode) against the recurrence a row: a row
    continues from its slot's state or starts from zeros, a row without a
    slot starts from zeros and writes nothing, tokens no row owns read
    zeros; every slot no row ends in and the other layer bit for bit."""
    T, t0, ql, hist, slots = _ROWS[rows]
    args = _draw(T, seed=T)
    pool = jax.random.normal(jax.random.PRNGKey(5), (2, 5, 4, 16, 16))
    fn = jax.jit(D.delta_rows, static_argnames=("backend", "interpret"))
    with jax.default_matmul_precision("highest"):
        o, new = fn(*args, *(jnp.asarray(a, jnp.int32)
                             for a in (t0, ql, hist, slots)), pool, 1,
                    backend=backend, interpret=True)
        want_o = np.zeros(o.shape, np.float32)
        want_pool = np.asarray(pool).copy()
        for a, n, h, slot in zip(t0, ql, hist, slots):
            if not n:
                continue
            held = slot < 5
            S0 = pool[1, slot] if held and h else jnp.zeros((4, 16, 16))
            ro, S = D.delta_recurrence(*(x[a:a + n] for x in args), S0)
            want_o[a:a + n] = np.asarray(ro)
            if held:
                want_pool[1, slot] = np.asarray(S)
    assert np.abs(np.asarray(o) - want_o).max() < 1e-5
    assert np.abs(np.asarray(new) - want_pool).max() < 2e-5
    assert np.array_equal(np.asarray(new[0]), np.asarray(pool[0]))
    ends = {s for s, n in zip(slots, ql) if n and s < 5}
    for slot in set(range(5)) - ends:
        assert np.array_equal(np.asarray(new[1, slot]),
                              np.asarray(pool[1, slot])), slot


@pytest.mark.parametrize("rows", ["unaligned", "passes", "no_slot_first"])
def test_chunk_kernel_in_interpret_mode_is_the_scan(rows):
    """The two forms of the second half run the same products in the same
    order: the kernel's outputs and pool are the scan's to a rounding."""
    T, *plan = _ROWS[rows]
    args = _draw(T, seed=3)
    pool = jax.random.normal(jax.random.PRNGKey(6), (2, 5, 4, 16, 16))
    fn = jax.jit(D.delta_rows, static_argnames=("backend", "interpret"))
    plan = [jnp.asarray(a, jnp.int32) for a in plan]
    with jax.default_matmul_precision("highest"):
        o0, p0 = fn(*args, *plan, pool, 1, backend="reference")
        o1, p1 = fn(*args, *plan, pool, 1, backend="pallas", interpret=True)
    assert float(jnp.abs(o1 - o0).max()) < 1e-6
    assert float(jnp.abs(p1 - p0).max()) < 1e-6
    assert float(jnp.abs(p0 - pool).max()) > 1e-2


@pytest.mark.parametrize("widths,why", [
    ((128, 96), "128 lanes"), ((64, 128), "equal")])
def test_chunk_kernel_refuses_by_name_what_mosaic_refuses(widths, why):
    dk, dv = widths
    z = lambda *shp: jnp.zeros(shp, jnp.float32)
    tab, count = D.chunk_table(
        jnp.zeros((1,), jnp.int32), jnp.full((1,), 64, jnp.int32),
        jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32), 1, 2)
    with pytest.raises(UnsupportedKernelGeometry, match=why):
        deltanet_chunk_tpu(
            z(1, 8, 64, dv), z(1, 8, 64, dk), z(1, 8, 64, dk),
            z(1, 8, 64, 64), z(1, 8, 64, dk), z(1, 8), z(1, 2, 8, dk, dv),
            z(8, dk, dv), 0, tab, count)


def test_rows_share_a_flat_axis_and_each_writes_its_own_slot_alone():
    """Two rows and a row without tokens on one axis: a row that continues
    from its slot's state, a row that starts from zeros in a slot that held
    something else; other slots and the other layer bit for bit."""
    q, k, v, g, b = _draw(150, seed=3)
    S0 = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (4, 16, 16))
    pool = jnp.zeros((2, 4, 4, 16, 16)).at[1, 2].set(S0).at[1, 0].set(7.0)
    pool = pool.at[1, 1].set(3.0).at[0].set(5.0)
    t0, ql = jnp.array([0, 70, 0]), jnp.array([70, 80, 0])
    hist, slots = jnp.array([5, 0, 0]), jnp.array([2, 0, 4])
    with jax.default_matmul_precision("highest"):
        o, new = jax.jit(D.delta_rows)(q, k, v, g, b, t0, ql, hist, slots,
                                       pool, 1)
        oa, Sa = D.delta_recurrence(q[:70], k[:70], v[:70], g[:70], b[:70],
                                    S0)
        ob, Sb = D.delta_recurrence(q[70:], k[70:], v[70:], g[70:], b[70:],
                                    jnp.zeros_like(S0))
    assert float(jnp.abs(o[:70] - oa).max()) < 1e-5
    assert float(jnp.abs(o[70:] - ob).max()) < 1e-5
    assert float(jnp.abs(new[1, 2] - Sa).max()) < 1e-5
    assert float(jnp.abs(new[1, 0] - Sb).max()) < 1e-5
    assert np.array_equal(np.asarray(new[0]), np.asarray(pool[0]))
    assert np.array_equal(np.asarray(new[1, 1]), np.asarray(pool[1, 1]))
    assert np.array_equal(np.asarray(new[1, 3]), np.asarray(pool[1, 3]))


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_what_lies_behind_a_rows_end_is_selected_out_not_multiplied_out(
        backend):
    """The tokens behind a row's last one in its last 64-token chunk are
    padding whose values nothing vouches for (found on the chip: a kernel
    leaves the rows it skips unwritten, and a NaN times a zero weight is a
    NaN): with NaN there, and in the slot a row that starts from zeros
    takes over, the row reads what it reads alone."""
    q, k, v, g, b = _draw(100, seed=11)
    bad = [a.at[70:].set(jnp.nan) for a in (q, k, v, g, b)]
    pool = jnp.full((1, 2, 4, 16, 16), jnp.nan)
    one = jnp.ones((1,), jnp.int32)
    with jax.default_matmul_precision("highest"):
        o, new = jax.jit(
            D.delta_rows, static_argnames=("backend", "interpret"))(
            *bad, 0 * one, 70 * one, 0 * one, one, pool, 0,
            backend=backend, interpret=True)
        want, S = D.delta_recurrence(
            q[:70], k[:70], v[:70], g[:70], b[:70], jnp.zeros((4, 16, 16)))
    assert float(jnp.abs(o[:70] - want).max()) < 1e-5
    assert float(jnp.abs(new[0, 1] - S).max()) < 1e-5
    assert bool(jnp.all(jnp.isfinite(o)))


@pytest.mark.parametrize("live", [
    (True, False, True), (True, True, True), (False, False, False),
    (False, False, True)], ids=["two", "all", "none", "last"])
def test_decode_kernel_in_interpret_mode_against_the_recurrence(live):
    """One pass of the kernel over the live slots: their states and outputs
    are the recurrence's, from a state that is not zero; idle slots and the
    other layer bit for bit, also when nothing is live."""
    args = _draw(3, seed=7)
    pool = jax.random.normal(jax.random.PRNGKey(8), (2, 4, 4, 16, 16))
    live = jnp.asarray(live)
    with jax.default_matmul_precision("highest"):
        o0, p0 = D.delta_decode(*args, pool, 1, live, backend="reference")
    o1, p1 = D.delta_decode(*args, pool, 1, live, backend="pallas",
                            interpret=True)
    assert float(jnp.abs(o1 - o0).max()) < 1e-5
    assert float(jnp.abs(p1 - p0).max()) < 1e-5
    idle = ~np.asarray(live)
    assert np.array_equal(np.asarray(p1[0]), np.asarray(pool[0]))
    assert np.array_equal(np.asarray(p1[1, :3][idle]),
                          np.asarray(pool[1, :3][idle]))
    assert np.array_equal(np.asarray(p1[1, 3]), np.asarray(pool[1, 3]))
    if bool(live.any()):
        assert float(jnp.abs(p1[1] - pool[1]).max()) > 1e-2


@pytest.mark.parametrize("geometry,why", [
    ((32, 64, 128, 96), "128 lanes"), ((32, 64, 64, 128), "equal"),
    ((3, 64, 128, 128), "divide"), ((2, 4, 128, 128), "blocks of 8")])
def test_kernel_geometry_mosaic_refuses_is_refused_by_name(geometry, why):
    check_deltanet_geometry(32, 64, 128, 128)
    with pytest.raises(UnsupportedKernelGeometry, match=why):
        check_deltanet_geometry(*geometry)


# ---- configuration, parameters, cache ---------------------------------------


def test_catalog_entry_is_the_published_config():
    c = CATALOG["ai-sage/GigaChat3.5-432B-A28B"]
    assert c is GIGACHAT35_432B
    assert (c.hidden_size, c.num_layers, c.num_heads, c.vocab_size,
            c.intermediate_size) == (7168, 40, 64, 128256, 18432)
    assert (c.kv_lora_rank, c.q_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, c.head_dim) == (
        512, 1536, 128, 64, 128, 192)
    assert (c.linear_key_heads, c.linear_value_heads, c.linear_key_dim,
            c.linear_value_dim, c.conv_kernel) == (32, 64, 128, 128, 4)
    assert (c.num_experts, c.num_experts_per_tok, c.expert_width,
            c.num_shared_experts, c.first_k_dense) == (256, 8, 2048, 1, 3)
    assert c.num_attn_layers == 10 and c.num_deltanet_layers == 30
    assert c.mixers[3] == c.mixers[39] == "attn" and c.mixers[0] == "deltanet"
    assert c.held_experts is None and c.num_held_experts == 256
    assert c.state_arrays() == (((3, 16384), "bfloat16"),
                                ((64, 128, 128), "float32"))
    assert c.deltanet_channels == 16384
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        row = next(r for r in map(json.loads, open(path))
                   if r["name"] == "GigaChat3.5-432B-A28B")
        assert ModelConfig.from_hf_config(row["config"], name=c.name) == c


def test_config_reads_the_keys_and_the_cut(model):
    cfg, _ = model
    assert cfg.mixers == ("deltanet", "attn", "deltanet", "deltanet",
                          "deltanet", "attn", "deltanet", "deltanet",
                          "deltanet")
    assert (cfg.num_experts, cfg.held_experts, cfg.num_held_experts) == (
        16, (0, 4), 4)
    assert cfg.moe_scoring == "sigmoid" and cfg.moe_expert_bias
    assert cfg.moe_renormalize and cfg.routed_scaling_factor == 2.5
    assert cfg.post_norms and cfg.norm_offset == 1.0 and cfg.attn_gate
    assert cfg.swiglu_limit == 10.0 and cfg.linear_gate_scale == 2.0
    assert cfg.q_lora_rank == 24 and cfg.state_mixer == "deltanet"
    kinds = [(r.mixer, r.moe, r.count, g.reps)
             for g in cfg.layer_runs() for r in g.runs]
    assert kinds == [("deltanet", False, 1, 1), ("attn", True, 1, 2),
                     ("deltanet", True, 3, 2)]
    assert cfg.loop_bodies == 3


@pytest.mark.parametrize("bad,match", [
    (dict(n_group=2, topk_group=1), "grouped top-k"),
    (dict(topk_method="group_limited_greedy"), "grouped top-k"),
    (dict(scoring_func="tanh"), "scoring_func"),
    (dict(layernorm_type="pre"), "pre_post"),
    (dict(held_experts=[0, 3]), "held_experts")])
def test_what_the_router_and_block_do_not_do_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(dict(HF, **bad))


def test_int8_tree_has_the_float_trees_structure(model):
    from helix_tpu.ops.quant import quantize_params

    cfg, params = model
    q = jax.eval_shape(lambda: quantize_params(params))
    seeded = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(1), int8=True))
    assert jax.tree.structure(q) == jax.tree.structure(seeded)
    ex = seeded["run02"]["experts"]["w_gate"]
    assert ex["weight"].shape == (6, 4, 64, 32)          # the HELD experts
    assert seeded["run02"]["router"]["weight"].shape == (6, 64, 16)
    assert seeded["run02"]["expert_bias"]["bias"].shape == (6, 16)


def test_logical_axes_name_every_tensor(model):
    cfg, params = model
    axes = param_logical_axes(cfg)
    flat = dict(jax.tree_util.tree_leaves_with_path(
        axes, is_leaf=lambda a: isinstance(a, tuple)))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        assert path in flat, path
        assert len(flat[path]) == leaf.ndim, path


def test_a_state_pool_stands_beside_a_latent_page_pool(model):
    cfg, _ = model
    cc = CacheConfig(num_pages=32, page_size=8, max_pages_per_seq=8,
                     dtype="float32", state_slots=3)
    ks, = cc.page_shapes(cfg)
    assert ks == (2, 8, 32 + 128)       # two latent layers, one array
    assert cc.state_shapes(cfg) == (((7, 3, 3, 128), "float32"),
                                    ((7, 3, 4, 16, 16), "float32"))
    cache = PagedKVCache.create(cfg, cc)
    assert cache.latent and cache.k_pages.shape == (2, 32, 8, 160)
    assert cache.v_pages is None
    conv, S = cache.state
    assert conv.shape == (7, 3, 3, 128) and S.dtype == jnp.float32
    assert cc.total_bytes(cfg) == 32 * (2 * 8 * 160 * 4) + (
        7 * 3 * (3 * 128 + 4 * 16 * 16) * 4)
    big = CacheConfig(num_pages=10240, page_size=16, max_pages_per_seq=160,
                      state_slots=64)
    assert big.state_bytes(GIGACHAT35_432B) == 30 * 64 * (
        64 * 128 * 128 * 4 + 3 * 16384 * 2)


def test_forward_without_a_cache_is_the_reference(model):
    """The program's forward (chunked delta form from zeros, absorbed latent
    attention, sorted grouped experts) against the reference's (recurrence,
    explicit K and V, a loop), and every fault of the reference over the
    limit."""
    cfg, params = model
    toks = jnp.asarray(tokens_of(80, 0))
    got, _ = forward(params, cfg, toks[None], jnp.arange(80)[None],
                     attn_fn=prefill_attn_fn)
    want = np.asarray(reference.forward(params, HF, toks))
    assert np.abs(np.asarray(got[0]) - want).max() < TOL
    for kw in (dict(state_bf16=True), dict(beta=False), dict(decay=False),
               dict(attn_gate=False), dict(drop_expert=1),
               dict(zero_state_at=64)):
        bad = np.asarray(reference.forward(params, HF, toks, **kw))
        assert _rel(bad[64:], want[64:]) > FAULT_LIMIT, kw


def test_compressed_gated_query_against_the_unabsorbed_form():
    """Every layer latent (no delta layer, no state pool): the compressed
    query's two products and norm and the output gate, absorbed over the
    latent, against explicit K and V; through the engine's latent cache
    too."""
    hf = dict(HF, num_hidden_layers=3, full_attention_layers=[0, 1, 2])
    cfg = dataclasses.replace(
        ModelConfig.from_hf_config(hf, name="tiny-latent"), dtype="float32")
    assert cfg.state_mixer is None and cfg.num_attn_layers == 3
    params = init_params(cfg, jax.random.PRNGKey(3))
    assert "wq_a" in params["run01"] and "wq" not in params["run01"]
    assert "attn_gate" in params["run01"]
    toks = tokens_of(40, 1)
    got, _ = forward(params, cfg, jnp.asarray(toks)[None],
                     jnp.arange(40)[None], attn_fn=prefill_attn_fn)
    want = np.asarray(reference.forward(params, hf, jnp.asarray(toks)))
    assert np.abs(np.asarray(got[0]) - want).max() < TOL
    no_gate = np.asarray(reference.forward(
        params, hf, jnp.asarray(toks), attn_gate=False))
    assert _rel(no_gate, want) > FAULT_LIMIT
    eng = _engine(cfg, params)
    req = _req("a", toks, 5)
    mine = _run(eng, [req], req)
    seq = jnp.asarray(toks + req.output_tokens)
    at = [len(toks) + n - 1 for n in sorted(mine)]
    full = np.asarray(reference.forward(params, hf, seq, rows=at))
    assert np.abs(np.stack([mine[n] for n in sorted(mine)]) - full).max() < TOL


# ---- held experts -----------------------------------------------------------


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST.  One expert layer, 16 routed experts, four ranks of
    four: each rank routes over all 16 at the published top-4 and computes
    its own experts' part (``moe_ffn`` under ``held_experts``); the parts
    add up to what a chip that held every expert computes, and to the
    reference's uncut layer with the shared expert (which every rank computes
    alike) counted once.  float32, a sum in another order: 1e-5 of outputs of
    size 0.05 (measured 2e-8)."""
    whole_cfg = dataclasses.replace(tiny(), held_experts=None)
    E, X, F, T = 64, 16, 32, 50
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
    with jax.default_matmul_precision("highest"):
        whole = moe_ffn(x, w_r, experts, whole_cfg, jax.nn.silu,
                        expert_bias=bias, backend="reference")
        parts, held_total, away_total = [], 0, 0
        for lo in range(0, X, 4):
            cfg = dataclasses.replace(whole_cfg, held_experts=(lo, lo + 4))
            mine = jax.tree.map(lambda a: a[lo:lo + 4], experts)
            part, stats = moe_ffn(x, w_r, mine, cfg, jax.nn.silu,
                                  expert_bias=bias, backend="reference",
                                  return_stats=True)
            parts.append(part)
            held_total += int(stats[1])
            away_total += int(stats[5])
            assert int(stats[1]) + int(stats[5]) == T * 4
            assert float(stats[3]) <= 4                # experts touched: held
        lp = {"router": {"weight": w_r[None]},
              "expert_bias": {"bias": bias[None]},
              "experts": jax.tree.map(lambda a: a[None], experts),
              "shared": shared}
        uncut = reference.expert_layer(
            x[0], lp, 0, dict(HF, held_experts=None), {})
        once = reference.glu(x[0], shared, 0, 10.0)
        shares = [reference.expert_layer(
            x[0], dict(lp, experts=jax.tree.map(
                lambda a: a[:, lo:lo + 4], lp["experts"])), 0,
            dict(HF, held_experts=[lo, lo + 4]), {"shared": False})
            for lo in range(0, X, 4)]
    assert held_total == T * 4 and away_total == 3 * T * 4
    assert float(jnp.abs(sum(parts) - whole).max()) < 1e-5
    assert float(jnp.abs(sum(parts)[0] + once - uncut).max()) < 1e-5
    assert float(jnp.abs(sum(shares) + once - uncut).max()) < 1e-5
    for part, share in zip(parts, shares):
        assert float(jnp.abs(part[0] - share).max()) < 1e-5
    assert float(jnp.abs(uncut).max()) > 1e-2
    # a rank's part is a part: none of them is the whole
    assert all(float(jnp.abs(p - whole).max()) > 1e-3 for p in parts)


def test_the_grouped_kernel_computes_a_ranks_part_in_interpret_mode():
    """The Pallas grouped product under held experts (the visit plan walks
    the held groups alone) against ``lax.ragged_dot``."""
    cfg = dataclasses.replace(tiny(), held_experts=(4, 8))
    E, X, F, T = 128, 16, 128, 24
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    x = jax.random.normal(ks[0], (1, T, E))
    w_r = jax.random.normal(ks[1], (E, X)) * 0.3
    experts = {n: {"weight": jax.random.normal(k, shp) * 0.05}
               for n, k, shp in (("w_gate", ks[2], (4, E, F)),
                                 ("w_up", ks[3], (4, E, F)),
                                 ("w_down", ks[4], (4, F, E)))}
    with jax.default_matmul_precision("highest"):
        want = moe_ffn(x, w_r, experts, cfg, jax.nn.silu,
                       backend="reference")
        got = moe_ffn(x, w_r, experts, cfg, jax.nn.silu, backend="pallas",
                      interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(want).max()) > 1e-3


def test_the_clamp_is_seen(model):
    """``swiglu_limit``: at a limit the activations pass, the layer changes."""
    cfg, params = model
    toks = jnp.asarray(tokens_of(20, 2))[None]
    a, _ = forward(params, cfg, toks, jnp.arange(20)[None],
                   attn_fn=prefill_attn_fn)
    b, _ = forward(params, dataclasses.replace(cfg, swiglu_limit=0.05),
                   toks, jnp.arange(20)[None], attn_fn=prefill_attn_fn)
    assert float(jnp.abs(a - b).max()) > 1e-3


# ---- the engine ---------------------------------------------------------------


def test_chunked_prefill_then_decode_through_both_pools_is_the_reference(
        model):
    """A 37-token prompt in three chunks beside a second request (mixed
    steps), then decode steps: next-token logits against the reference's
    full forward at every step, and each fault over the limit at every
    step."""
    cfg, params = model
    eng = _engine(cfg, params)
    prompt = tokens_of(37, 0)
    req, other = _req("a", prompt, 7), _req("b", tokens_of(11, 1), 9)
    got = _run(eng, [req, other], req)
    assert len(got) >= 6 and eng.num_mixed_steps >= 1
    assert eng.mixer_counts["chunk_rows"] >= 4
    assert eng.mixer_counts["decode_rows"] >= 12
    per_slot = eng.recurrent_state_bytes // 3
    assert per_slot == 7 * (3 * 128 + 4 * 16 * 16) * 4
    assert eng.mixer_counts["state_bytes_touched"] == 2 * per_slot * (
        eng.mixer_counts["decode_rows"] + eng.mixer_counts["chunk_rows"])
    # every (token, choice) of every expert layer is counted, here or away
    eng._drain_moe_drops()
    assert eng.moe_routed_tokens > 0 and eng.moe_away_tokens > 0
    assert (eng.moe_routed_tokens + eng.moe_away_tokens) % (8 * 4) == 0
    seq = jnp.asarray(prompt + req.output_tokens)
    at = [len(prompt) + n - 1 for n in sorted(got)]
    mine = np.stack([got[n] for n in sorted(got)])
    want = np.asarray(reference.forward(params, HF, seq, rows=at))
    assert np.abs(mine - want).max() < TOL
    for kw in (dict(state_bf16=True), dict(beta=False), dict(decay=False),
               dict(attn_gate=False), dict(drop_expert=1),
               dict(zero_state_at=32)):
        bad = np.asarray(reference.forward(params, HF, seq, rows=at, **kw))
        least = min(_rel(b, w) for b, w in zip(bad, want))
        assert least > FAULT_LIMIT, (kw, least)
        assert max(_rel(m, w) for m, w in zip(mine, want)) < least / 100


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
    import joint_pass

    eng = _decoding(model)
    joint_pass.assert_one_forward(eng, 16, 1, "dot_general", "mlp.down")
    joint_pass.assert_one_forward(
        eng, 16, 1, "dot_general", "deltanet.out_proj")
    joint_pass.assert_one_forward(eng, 16, 1, "dot_general", "attn.q_b")


def test_a_chunk_beside_decode_rows_is_the_chunk_then_the_decode_step(model):
    import joint_pass

    cfg, params = model

    def reqs():
        return [_req("s", tokens_of(9, 5), 14), _req("x", tokens_of(40, 4))]

    joint_pass.assert_mixed_is_chunk_then_decode(
        lambda **kw: _engine(cfg, params, **kw), reqs, "x", TOL)


def test_a_wave_of_inert_rows_leaves_the_decode_state_and_the_pools(model):
    import joint_pass

    eng = _decoding(model)
    before = [np.asarray(a) for a in eng.cache.state]
    joint_pass.assert_inert_wave_keeps_decode_state(eng, 16)
    for a, b in zip(before, eng.cache.state):
        assert np.array_equal(a, np.asarray(b))
    assert before[0].any() and before[1].any()


def test_a_wave_beside_running_rows_is_the_wave_then_the_decode_step(model):
    """The running rows' conv tails and matrix states take one step inside
    the wave's pass; the row out of headroom keeps its state bit for bit."""
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
    cfg, params = model
    eng = _engine(cfg, params, max_decode_batch=1)
    first, second = _req("x", tokens_of(21, 6)), _req("y", tokens_of(19, 7))
    _run(eng, [first], first)
    assert float(jnp.max(jnp.abs(eng.cache.state[1]))) > 0
    got = _run(eng, [second], second)
    fresh_req = _req("y", tokens_of(19, 7))
    fresh = _run(_engine(cfg, params, max_decode_batch=1), [fresh_req],
                 fresh_req)
    assert second.output_tokens == fresh_req.output_tokens
    for n in got:
        assert np.abs(got[n] - fresh[n]).max() < TOL


def test_idle_slots_and_padding_leave_the_pools_bit_for_bit(model):
    cfg, params = model
    eng = _engine(cfg, params)
    req = _req("a", tokens_of(13, 8), 5)      # 13 tokens in a rung of 16
    eng.add_request(req)
    before = None
    while eng.has_work():
        eng.step()
        now = tuple(np.asarray(a) for a in eng.cache.state)
        if before is not None and req.slot is not None:
            idle = [i for i in range(3) if i != req.slot]
            for a, b in zip(now, before):
                assert np.array_equal(a[:, idle], b[:, idle])
        before = now
    assert before is not None and all(np.any(a) for a in before)
    idle = [i for i in range(3) if i != 0]
    assert not any(np.any(a[:, idle]) for a in before)


REFUSED_SETTINGS = {
    "int8_kv": (dict(kv_cache_dtype="int8"), "kv_cache_dtype int8"),
    "adapters": (dict(adapter_pool_slots=2), "adapter_pool_slots"),
    "speculation": (dict(enable_spec_decode=True), "enable_spec_decode"),
    "host_tier": (dict(host_pool_bytes=1 << 20), "host_pool_bytes"),
    "prefix_cache": (dict(enable_prefix_cache=True), "enable_prefix_cache"),
}


@pytest.mark.parametrize("name", sorted(REFUSED_SETTINGS))
def test_what_cannot_carry_the_states_is_refused_by_name(model, name):
    cfg, params = model
    kw, setting = REFUSED_SETTINGS[name]
    with pytest.raises(UnsupportedForModel, match=setting):
        _engine(cfg, params, **kw)


def test_a_mesh_is_refused_by_name(model):
    from helix_tpu.engine.engine import refuse_unsupported

    cfg, _ = model

    class TwoDevices:
        devices = np.zeros((2,))

    no_mla = dataclasses.replace(cfg, kv_lora_rank=0)
    with pytest.raises(UnsupportedForModel, match="gated delta rule"):
        refuse_unsupported(no_mla, EngineConfig(
            enable_prefix_cache=False), TwoDevices())
    only_held = dataclasses.replace(no_mla, layer_types=None)
    with pytest.raises(UnsupportedForModel, match="held experts"):
        refuse_unsupported(only_held, EngineConfig(
            enable_prefix_cache=False), TwoDevices())


@pytest.mark.parametrize("call", ["export_request", "export_prefill",
                                  "kv_filestore"])
def test_calls_that_move_pages_are_refused_by_name(model, call):
    cfg, params = model
    eng = _engine(cfg, params)
    with pytest.raises(UnsupportedForModel, match="gated delta rule"):
        if call == "kv_filestore":
            eng.kv_filestore = object()
        else:
            getattr(eng, call)("nobody")


def test_launch_record_and_metrics_carry_the_new_fields(model):
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
        req = _req("a", tokens_of(9, 9), 3)
        _run(eng, [req], req)
    finally:
        obs_trace.phase = orig
    assert seen and all(
        kw["deltanet_layers"] == 7 and kw["attn_layers"] == 2
        and kw["held_experts"] == 4 and "conv_layers" not in kw
        and "retention_layers" not in kw for kw in seen)
    assert eng.recurrent_state_bytes == 7 * 3 * (3 * 128 + 4 * 16 * 16) * 4


# ---- spans, the flight record, /metrics --------------------------------------

SCOPES = ("deltanet.in_proj", "deltanet.conv", "deltanet.mix",
          "deltanet.out_proj", "attn.q_a", "attn.q_b", "attn.gate",
          "attn.kernel", "moe.experts", "moe.shared")


@pytest.fixture(scope="module")
def lowered_text(model):
    import joint_pass

    fn, args = joint_pass.step_program(_decoding(model), 16, 1)
    return fn.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_step_carries_the_named_scope(lowered_text, scope):
    import re

    assert re.search(rf"[/\"]{re.escape(scope)}[/\"]", lowered_text), scope


def test_chunks_of_the_delta_rule_are_counted_from_the_hosts_mirrors(model):
    """``helix_deltanet_chunks_total``: a launch's prefill rows' ``ceil(tokens
    / 64)`` x delta layers on the launch's span and, summed, on the engine;
    by hand for a 150-token prompt in chunks of 128 and three decode steps."""
    from helix_tpu.obs import trace as obs_trace

    cfg, params = model
    eng = _engine(cfg, params, max_prefill_len=128, max_pages_per_seq=24)
    seen = []
    orig = obs_trace.phase

    def phase(name, *a, **kw):
        if name == "helix.loop.launch":
            seen.append(kw["deltanet_chunks"])
        return orig(name, *a, **kw)

    obs_trace.phase = phase
    try:
        eng.add_request(_req("c", tokens_of(150, 4), 4))
        while eng.has_work():
            eng.step()
    finally:
        obs_trace.phase = orig
    # 128 tokens are two chunks, the 22 left one, in each of 7 delta layers
    assert seen[:2] == [2 * 7, 1 * 7] and not any(seen[2:]), seen
    assert eng.mixer_counts["chunks"] == 3 * 7


def test_flight_records_and_metrics_carry_the_new_series(model):
    """Through the serving loop and the HTTP surface's collector: the step's
    flight record says how many delta layers and held experts the model has,
    and ``/metrics`` renders the rows the state pool's steps advanced, the
    bytes they moved, the pool's size, and the assignments here and away."""
    import threading

    from helix_tpu.serving.engine_loop import EngineLoop
    from helix_tpu.serving.openai_api import OpenAIServer
    from helix_tpu.serving.registry import ModelRegistry, ServedModel
    from helix_tpu.serving.tokenizer import ByteTokenizer

    cfg, params = model
    eng = _engine(cfg, params)
    loop = EngineLoop(eng, "tiny-gigachat")      # never started: inline
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
        r["deltanet_layers"] == 7 and r["held_experts"] == 4
        and r["attn_layers"] == 2 and r["conv_layers"] == 0
        for r in records)
    registry = ModelRegistry()
    registry.register(ServedModel(
        name="tiny-gigachat", loop=loop, tokenizer=ByteTokenizer(),
        context_length=128))
    text = OpenAIServer(registry).obs.render()

    def value(series, label=""):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(series) and label in ln)
        return float(line.rsplit(" ", 1)[1])

    assert value("helix_deltanet_rows_total{", 'kind="chunk"') == 2
    # a 21-token prompt in chunks of 16: two rows of one 64-token chunk each
    assert value("helix_deltanet_chunks_total{") == 2 * 7
    assert sum(r["deltanet_chunks"] for r in records) == 2 * 7
    assert value("helix_deltanet_rows_total{", 'kind="decode"') >= 4
    assert value("helix_recurrent_state_bytes{") == eng.recurrent_state_bytes
    assert value("helix_state_bytes_touched_total{") == (
        eng.mixer_counts["state_bytes_touched"]) > 0
    held = value("helix_moe_held_tokens_total{")
    away = value("helix_moe_away_tokens_total{")
    assert held == eng.moe_routed_tokens > 0 and away > held
    assert "helix_retention_rows_total" not in text
