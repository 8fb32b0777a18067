"""Qwen3-Next-style hybrid decoders on the CPU at a small size, float32,
seeded weights: gated delta-rule layers (a conv tail and a matrix state a
slot, the output ``silu(z)`` on a plain-gain norm) beside gated GQA attention
layers (pages; zero-centred q/k norms, rope over a quarter of a head, a sigmoid
gate a head and channel), and in every layer routed experts behind a softmax
router, of which the chip holds one expert-parallel rank's, beside a shared
expert under a sigmoid gate.  The oracle is the benchmark's plain reference
(``benchmark/lib/reference_deltanet_gqa_moe_decoder.py``: the token-by-token
recurrence, explicit scores, every held expert a dense product); the engine is
compared by LOGITS, the reference run on the PROGRAM'S OWN expert choices
(``models.moe.PROBE``), and the choices themselves are held to the reference's
probabilities."""

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
    reference_deltanet_gqa_moe_decoder as reference,
)
from helix_tpu.engine import engine as engine_mod  # noqa: E402
from helix_tpu.engine.engine import (  # noqa: E402
    Engine, EngineConfig, Request, SamplingParams, UnsupportedForModel,
)
from helix_tpu.engine.kv_cache import CacheConfig, PagedKVCache  # noqa: E402
from helix_tpu.models import moe  # noqa: E402
from helix_tpu.models.common import (  # noqa: E402
    CATALOG, QWEN3_NEXT_80B, ModelConfig,
)
from helix_tpu.models.llama import (  # noqa: E402
    forward, init_params, param_logical_axes, prefill_attn_fn,
)
from helix_tpu.testing.moe_probe import Probe  # noqa: E402

# the catalog row's ``config`` (model-configs guide, row
# Qwen3-Next-80B-A3B-Instruct), copied here letter for letter
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}

# the small model: two periods, 4 / 8 delta heads of 16, 4 / 2 attention heads
# of 32 with 8 rotated, 16 experts of width 32 at top-4 of which [0, 8) are
# held here
HF = dict(
    model_type="qwen3_next", vocab_size=256, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    partial_rotary_factor=0.25, full_attention_interval=4,
    linear_conv_kernel_dim=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_num_key_heads=4,
    linear_num_value_heads=8, num_experts=8, published_num_experts=16,
    held_experts=[0, 8], num_experts_per_tok=4, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], rms_norm_eps=1e-6,
    rope_theta=10000000, rope_scaling=None, tie_word_embeddings=False,
    use_sliding_window=False, max_position_embeddings=512, hidden_act="silu",
)
L, K = HF["num_hidden_layers"], HF["num_experts_per_tok"]
# THE TIGHT ONE: float32, the same mathematics on the same experts through
# another order of operations (a state carried through 64-token chunks and
# single steps against a token-by-token scan; pages against explicit scores; a
# sorted grouped product against dense products over every token): measured
# 1e-7 of the logits' spread of 0.16 (relative RMS a step); bfloat16 products
# in place of float32 ones read over a hundred times the tolerance (``test_bfloat16_products_fail_the_tight_tolerance``)
TOL = 1e-5
# THE LOOSE ONE holds the choices: where the program's four and the
# reference's differ, the expert in one and not the other has a probability
# within this share of the reference's fourth.  float32 both sides: a flip
# needs two probabilities closer than the two sides' rounding, 1e-6 of a
# probability of 0.06; a wrong router (another expert's column) misses by the
# probabilities' own spread
CHOICE_TOL = 1e-4
# the least any control reads at this size, at its least step, is ONE dropped
# expert's 3.4e-5 (relative RMS; the bfloat16 state 2e-4, the gate a head 3e-4,
# the renormalisation 7e-4, the rest 2e-3 and over): the limit lies under it,
# over the tight tolerance and two hundred times over the engine's own error
FAULT_LIMIT = 2e-5


def tiny(**kw):
    cfg = ModelConfig.from_hf_config(dict(HF, **kw), name="tiny-qwen3-next")
    return dataclasses.replace(cfg, dtype="float32")


def _off_init(params, seed=2):
    """Norm gains off their initial value, so that a gain read as ``w`` and
    not ``1 + w`` (or the other way) is seen."""
    k = jax.random.PRNGKey(seed)
    for key in ("run00", "run01"):
        for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm", "o_norm"):
            if name in params[key]:
                k, sub = jax.random.split(k)
                w = params[key][name]["weight"]
                params[key][name]["weight"] = w + 0.1 * jax.random.normal(
                    sub, w.shape, w.dtype)
    return params


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, _off_init(init_params(cfg, jax.random.PRNGKey(1)))


@pytest.fixture(scope="module", autouse=True)
def hooked():
    """The hook is set for the WHOLE module (a step program is traced with
    its callbacks once a shape and found again by the next test: the callback
    looks ``models.moe.PROBE`` up when it fires, so a test swaps in a Probe of
    its own without a new trace) and taken away, with every program traced
    under it, when the module is done."""
    engine_mod._build_ragged_step_fn.cache_clear()
    moe.PROBE = Probe()
    yield
    moe.PROBE = None
    engine_mod._build_ragged_step_fn.cache_clear()


@pytest.fixture
def probe():
    moe.PROBE = mine = Probe()
    yield mine
    moe.PROBE = Probe()


def tokens_of(n, seed=0, lo=1, hi=256):
    return np.random.default_rng(seed).integers(lo, hi, size=n).tolist()


def _engine(cfg, params, **kw):
    ecfg = EngineConfig(**{**dict(
        max_decode_batch=3, page_size=16, num_pages=96, max_pages_per_seq=16,
        max_prefill_len=32, attn_backend="reference",
        enable_prefix_cache=False), **kw})
    return Engine(cfg, params, ecfg)


def _req(rid, prompt, n=6, **kw):
    return Request(id=rid, prompt_tokens=prompt, sampling=SamplingParams(
        max_tokens=n, temperature=0.0, **kw))


def _drive(eng, reqs, probe=None, later=()):
    """Run to the end; every request's next-token logits after each step it
    decoded in, ``{id: {tokens out so far: logits [V]}}``.  ``later``:
    requests added once the first of ``reqs`` has finished (a reused slot)."""
    later = list(later)
    logits = {r.id: {} for r in [*reqs, *later]}
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            eng.add_request(r)
        while eng.has_work() or later:
            if later and reqs[0].finished:
                eng.add_request(later[0])
                reqs, later = [*reqs, later[0]], later[1:]
            if probe is not None:
                probe.mark("step")
            eng.step()
            jax.effects_barrier()
            live = [r for r in reqs if r.output_tokens and r.slot is not None
                    and eng.slots[r.slot] is r]
            if not live:
                continue
            if probe is not None:
                probe.mark("peek")
            got = np.asarray(eng.next_token_logits())
            jax.effects_barrier()
            for r in live:
                logits[r.id].setdefault(len(r.output_tokens), got[r.slot])
    return logits


def _rel(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))


def compare(params, probe, req, logits, hf=HF, **faults):
    """One finished request against the reference ON THE PROGRAM'S OWN
    CHOICES: ``(worst relative RMS of a step's logits, positions where the
    two sides' choices differ, the worst such miss as a share of the
    reference's k-th probability, the reference's rows)``."""
    seq = req.prompt_tokens + req.output_tokens
    choices = probe.choices(seq, L, K)
    # every position that was an input has its choices (the last token out
    # was never one)
    assert (choices[:, :len(seq) - 1] >= 0).all()
    at = sorted(logits)
    rows = [len(req.prompt_tokens) + n - 1 for n in at]
    want, router = reference.forward(
        params, hf, jnp.asarray(seq), rows=rows, choices=choices,
        return_router=True, **faults)
    want = np.asarray(want)
    got = np.stack([logits[n] for n in at])
    worst = max(_rel(g, w) for g, w in zip(got, want))
    own, p_own, p_used = (np.asarray(router[k])
                          for k in ("own", "p_own", "p_used"))
    differ, miss = 0, 0.0
    for l in range(L):
        for p in range(len(seq) - 1):
            mine, theirs = set(choices[l, p].tolist()), set(own[l, p].tolist())
            if mine == theirs:
                continue
            differ += 1
            kth = p_own[l, p, -1]
            used = dict(zip(np.asarray(router["used"])[l, p].tolist(),
                            p_used[l, p]))
            theirs_p = dict(zip(own[l, p].tolist(), p_own[l, p]))
            for e in mine ^ theirs:
                pe = used[e] if e in used else theirs_p[e]
                miss = max(miss, abs(pe - kth) / kth)
    return worst, differ, miss, want


# ---- the configuration --------------------------------------------------------


def test_the_catalog_rows_config_verbatim_gives_the_profiles_fields():
    cfg = ModelConfig.from_hf_config(
        PUBLISHED, name="Qwen/Qwen3-Next-80B-A3B-Instruct")
    assert cfg == QWEN3_NEXT_80B
    assert CATALOG["Qwen/Qwen3-Next-80B-A3B-Instruct"] is QWEN3_NEXT_80B
    assert cfg.layer_types == ("deltanet", "deltanet", "deltanet", "attn") * 12
    assert cfg.rotary_dim == 64 and cfg.rope_theta == 1e7
    assert cfg.rope_scaling is None and cfg.rope_of("attn")[0] == 64
    assert cfg.qk_norm and cfg.norm_offset == 1.0
    assert cfg.attn_gate and cfg.attn_gate_channels
    assert (cfg.moe_scoring, cfg.moe_renormalize) == ("softmax", True)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (512, 10)
    assert cfg.num_shared_experts == 1 and cfg.shared_expert_gate
    assert cfg.expert_width == 512 and cfg.first_k_dense == 0
    assert cfg.ffns == ("moe",) * 48
    assert (cfg.linear_key_heads, cfg.linear_value_heads) == (16, 32)
    assert cfg.deltanet_channels == 8192 and cfg.conv_kernel == 4
    assert cfg.linear_gate == "silu"
    assert cfg.state_mixer == "deltanet" and cfg.num_attn_layers == 12
    # one slot's state in one layer: the conv tail and the float32 matrices
    (tail, tail_dt), (mat, mat_dt) = cfg.state_arrays()
    assert tail == (3, 8192) and mat == (32, 128, 128)
    assert jnp.dtype(mat_dt) == jnp.float32


def test_the_cut_is_the_first_stage_as_one_rank_of_two():
    cut = ModelConfig.from_hf_config(dict(
        PUBLISHED, num_hidden_layers=12, num_experts=256,
        published_num_experts=512, held_experts=[0, 256]))
    assert cut.num_experts == 512 and cut.held_experts == (0, 256)
    assert cut.num_held_experts == 256
    assert cut.num_state_layers == 9 and cut.num_attn_layers == 3
    # three periods run as ONE group of two loop bodies
    (group,) = cut.layer_runs()
    assert group.reps == 3 and [r.count for r in group.runs] == [3, 1]
    assert cut.loop_bodies == 2
    per_slot = sum(int(np.prod(s)) * jnp.dtype(d).itemsize
                   for s, d in cut.state_arrays()) * cut.num_state_layers
    assert per_slot == 19316736


@pytest.mark.parametrize("bad,match", [
    (dict(decoder_sparse_step=2), "decoder_sparse_step"),
    (dict(mlp_only_layers=[0]), "mlp_only_layers"),
    (dict(use_sliding_window=True), "use_sliding_window"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(shared_expert_intermediate_size=48), "shared expert"),
    (dict(layer_types=["sliding_attention"] * 8), "layer_types"),
    (dict(held_experts=[0, 4]), "held_experts"),
])
def test_what_from_hf_config_does_not_serve_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(dict(HF, **bad))


def test_layer_types_where_given_say_the_kinds_outright():
    kinds = ["linear_attention", "full_attention"] * 4
    cfg = ModelConfig.from_hf_config(dict(HF, layer_types=kinds))
    assert cfg.layer_types == ("deltanet", "attn") * 4


def test_int8_tree_has_the_float_trees_structure_and_axes(model):
    cfg, params = model
    q = init_params(cfg, jax.random.PRNGKey(1), int8=True)
    strip = lambda t: {k: v for k, v in t.items()
                       if k not in ("scale", "embed_scale")}
    for key in ("run00", "run01"):
        for name, leaf in params[key].items():
            got = q[key][name]
            if "weight" in leaf and "weight" in got:
                assert got["weight"].shape == leaf["weight"].shape, name
            assert set(strip(got) if "weight" in got else got) == set(leaf)
    axes = param_logical_axes(cfg)
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_a = jax.tree_util.tree_flatten_with_path(
        axes, is_leaf=lambda x: isinstance(x, tuple))[0]
    assert [p for p, _ in flat_p] == [p for p, _ in flat_a]
    for (_, leaf), (_, ax) in zip(flat_p, flat_a):
        assert leaf.ndim == len(ax)
    # the new tensors, at their widths
    assert params["run01"]["attn_gate"]["weight"].shape == (2, 64, 4 * 32)
    assert params["run00"]["shared_gate"]["weight"].shape == (6, 64, 1)


def test_a_state_pool_stands_beside_a_gqa_page_pool(model):
    cfg, _ = model
    cc = CacheConfig(num_pages=8, page_size=16, max_pages_per_seq=4,
                     dtype="float32", state_slots=3)
    cache = PagedKVCache.create(cfg, cc)
    # pages of the two attention layers alone; a state a slot of the six
    # delta layers: the conv tail and the float32 matrices
    assert not cache.latent
    assert cache.k_pages.shape[:2] == cache.v_pages.shape[:2] == (2, 8)
    assert cc.state_shapes(cfg) == (((6, 3, 3, 256), "float32"),
                                    ((6, 3, 8, 16, 16), "float32"))
    tail, mats = cache.state
    assert tail.shape == (6, 3, 3, 256) and mats.dtype == jnp.float32
    assert cc.total_bytes(cfg) == 8 * (2 * 2 * 16 * 2 * 32 * 4) + (
        6 * 3 * (3 * 256 + 8 * 16 * 16) * 4)
    # the cell's pools: 3 layers of pages beside 9 layers of state
    big = CacheConfig(num_pages=16897, page_size=16, max_pages_per_seq=1056,
                      state_slots=16)
    cut = dataclasses.replace(
        QWEN3_NEXT_80B, num_layers=12,
        layer_types=QWEN3_NEXT_80B.layer_types[:12], held_experts=(0, 256))
    assert big.state_bytes(cut) == 16 * 19316736
    assert big.total_bytes(cut) - big.state_bytes(cut) == 16897 * 98304


# ---- the loader ---------------------------------------------------------------


def test_the_published_files_interleaved_projections_are_parted_at_load():
    """``models/loader.py::_qwen3_next_tree`` on a seeded state dict under the
    published names (no checkpoint is mounted): every output row of an
    interleaved projection carries a CODE that says what it is (kind, head,
    channel), and the tree's columns must hold the codes in the program's
    order: ``in_qkv`` = q | k | v heads-major, ``in_z``, ``in_b``, ``in_a``
    by value head, ``wq`` / ``attn_gate`` a head's halves of ``q_proj``; a
    stack holds its run's layers repetition-major; only the held experts are
    read."""
    from helix_tpu.models.loader import _qwen3_next_tree

    cfg = tiny()
    E, nk, nv, dk, dv, H, D = 64, 4, 8, 16, 16, 4, 32
    r = nv // nk
    rng = np.random.default_rng(0)
    sd, code = {}, {"q": 1e3, "k": 2e3, "v": 3e3, "z": 4e3, "b": 5e3,
                    "a": 6e3, "wq": 7e3, "gate": 8e3}

    def rows(values):
        """``[out, E]``: every input column of row j holds values[j]."""
        return np.repeat(np.asarray(values, np.float32)[:, None], E, axis=1)

    for l in range(8):
        at = f"model.layers.{l}."
        sd[at + "input_layernorm.weight"] = np.full((E,), l, np.float32)
        sd[at + "post_attention_layernorm.weight"] = rng.normal(size=E)
        if l % 4 != 3:
            qkvz, ba = [], []
            for g in range(nk):
                qkvz += [code["q"] + g * dk + d for d in range(dk)]
                qkvz += [code["k"] + g * dk + d for d in range(dk)]
                qkvz += [code["v"] + (g * r + j) * dv + d
                         for j in range(r) for d in range(dv)]
                qkvz += [code["z"] + (g * r + j) * dv + d
                         for j in range(r) for d in range(dv)]
                ba += [code["b"] + g * r + j for j in range(r)]
                ba += [code["a"] + g * r + j for j in range(r)]
            la = at + "linear_attn."
            sd[la + "in_proj_qkvz.weight"] = rows(qkvz)
            sd[la + "in_proj_ba.weight"] = rows(ba)
            sd[la + "conv1d.weight"] = rng.normal(
                size=(2 * nk * dk + nv * dv, 1, 4))
            sd[la + "A_log"] = rng.normal(size=nv)
            sd[la + "dt_bias"] = rng.normal(size=nv)
            sd[la + "norm.weight"] = rng.normal(size=dv)
            sd[la + "out_proj.weight"] = rng.normal(size=(E, nv * dv))
        else:
            sa = at + "self_attn."
            sd[sa + "q_proj.weight"] = rows(
                [code[part] + h * D + d for h in range(H)
                 for part in ("wq", "gate") for d in range(D)])
            sd[sa + "k_proj.weight"] = rng.normal(size=(2 * D, E))
            sd[sa + "v_proj.weight"] = rng.normal(size=(2 * D, E))
            sd[sa + "o_proj.weight"] = rng.normal(size=(E, H * D))
            sd[sa + "q_norm.weight"] = rng.normal(size=D)
            sd[sa + "k_norm.weight"] = rng.normal(size=D)
        sd[at + "mlp.gate.weight"] = rng.normal(size=(16, E))
        for e in range(16):
            for nm, shp in (("gate_proj", (32, E)), ("up_proj", (32, E)),
                            ("down_proj", (E, 32))):
                sd[at + f"mlp.experts.{e}.{nm}.weight"] = np.full(
                    shp, 100 * l + e, np.float32)
        for nm, shp in (("gate_proj", (32, E)), ("up_proj", (32, E)),
                        ("down_proj", (E, 32))):
            sd[at + f"mlp.shared_expert.{nm}.weight"] = rng.normal(size=shp)
        sd[at + "mlp.shared_expert_gate.weight"] = rng.normal(size=(1, E))
    sd["model.embed_tokens.weight"] = rng.normal(size=(256, E))
    sd["model.norm.weight"] = rng.normal(size=E)
    sd["lm_head.weight"] = rng.normal(size=(256, E))
    sd["mtp.fc.weight"] = rng.normal(size=(E, 2 * E))      # never read
    read = []

    def get(name):
        read.append(name)
        return np.asarray(sd[name], np.float32)

    tree = _qwen3_next_tree(
        cfg, get, lambda n: np.ascontiguousarray(get(n).T))
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(
        lambda a: a.shape, want)
    d, a = tree["run00"], tree["run01"]
    # stacks are repetition-major: delta layers 0 1 2 4 5 6, attention 3 7
    assert d["attn_norm"]["weight"][:, 0].tolist() == [0, 1, 2, 4, 5, 6]
    assert a["attn_norm"]["weight"][:, 0].tolist() == [3, 7]
    cols = lambda kind, n: [code[kind] + j for j in range(n)]
    for i in range(6):
        assert d["in_qkv"]["weight"][i, 0].tolist() == (
            cols("q", nk * dk) + cols("k", nk * dk) + cols("v", nv * dv))
        assert d["in_z"]["weight"][i, 5].tolist() == cols("z", nv * dv)
        assert d["in_b"]["weight"][i, 0].tolist() == cols("b", nv)
        assert d["in_a"]["weight"][i, 0].tolist() == cols("a", nv)
    assert d["conv"]["taps"].shape == (6, 2 * nk * dk + nv * dv, 4)
    np.testing.assert_array_equal(
        d["conv"]["taps"][1],
        sd["model.layers.1.linear_attn.conv1d.weight"][:, 0].astype(
            np.float32))
    for i in range(2):
        assert a["wq"]["weight"][i, 0].tolist() == cols("wq", H * D)
        assert a["attn_gate"]["weight"][i, 9].tolist() == cols("gate", H * D)
    # experts [0, 8) of 16, of the right layer; 8..15 and mtp.* never read
    got = d["experts"]["w_up"]["weight"]
    assert got.shape == (6, 8, E, 32)
    assert got[3, :, 0, 0].tolist() == [400 + e for e in range(8)]
    assert a["experts"]["w_down"]["weight"][1, :, 0, 0].tolist() == [
        700 + e for e in range(8)]
    assert d["shared_gate"]["weight"].shape == (6, E, 1)
    assert not any(".experts.8." in n or n.startswith("mtp.") for n in read)


# ---- the model ----------------------------------------------------------------


def test_forward_without_a_cache_is_the_reference(model):
    cfg, params = model
    toks = tokens_of(70, 3)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, jnp.asarray(toks)[None],
                         jnp.arange(70)[None], attn_fn=prefill_attn_fn)
    want = np.asarray(reference.forward(params, HF, jnp.asarray(toks)))
    assert np.abs(np.asarray(got[0]) - want).max() < TOL
    assert want.std() > 0.05


# what each case runs: (requests as (prompt tokens, tokens out), requests
# added when the first has finished, engine keywords)
CASES = {
    # 20 tokens in one chunk of 32, then steps
    "a_prompt_inside_one_chunk": ([(20, 6)], [], {}),
    # 150 tokens in five chunks of 32: the state and the conv tail carried,
    # pages with history from the second on
    "a_prompt_of_five_chunks": ([(150, 5)], [], {}),
    # a short request decodes while a long one's chunks pass: mixed steps
    "a_mixed_step_with_a_chunk_and_decode_rows": (
        [(9, 10), (70, 5)], [], {}),
    # windows of four fused decode steps
    "the_fused_decode_window": (
        [(27, 12), (12, 12)], [],
        dict(decode_steps_per_sync=4, adaptive_sync_max_streams=0)),
    # one slot: the second request takes the slot the first one left
    "a_slot_reused_by_a_second_request": (
        [(37, 4)], [(21, 4)], dict(max_decode_batch=1)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_through_the_state_pool_and_the_pages_is_the_reference(
        case, model, probe):
    """Chunked prefill then decode through the state pool and the pages
    against the reference's full forward, by logits at every step a request
    decoded in: tight on the program's own choices, and the choices held to
    the reference's probabilities."""
    cfg, params = model
    first, then, kw = CASES[case]
    eng = _engine(cfg, params, **kw)
    # (position, token) names a record: a request's ids come from its own
    # eighth of the vocabulary's residues
    mk = lambda i, n, out: _req(
        f"r{i}", [8 * t + i for t in tokens_of(n, 10 + i, 1, 32)], out)
    reqs = [mk(i, n, out) for i, (n, out) in enumerate(first)]
    later = [mk(len(reqs) + i, n, out) for i, (n, out) in enumerate(then)]
    logits = _drive(eng, reqs, probe, later)
    assert probe.conflicts == 0
    for r in [*reqs, *later]:
        assert len(logits[r.id]) >= 2, r.id
        worst, differ, miss, want = compare(params, probe, r, logits[r.id])
        assert worst < TOL, (r.id, worst)
        assert miss < CHOICE_TOL, (r.id, differ, miss)
        assert want.std() > 0.05
    if "mixed" in case:
        assert eng.num_mixed_steps >= 1
    if "fused" in case:
        # a window put out several tokens between two host syncs
        ns = sorted(logits[reqs[0].id])
        assert max(b - a for a, b in zip(ns, ns[1:])) >= 2, ns
    if "reused" in case:
        # the second request's logits are those it gets in a fresh engine:
        # the state it found was zeroed, not inherited
        solo = _engine(cfg, params, **kw)
        again = _req("solo", later[0].prompt_tokens, 4)
        alone = _drive(solo, [again])
        for n, lg in alone["solo"].items():
            assert np.abs(lg - logits[later[0].id][n]).max() < TOL


@pytest.fixture(scope="module")
def finished(model):
    """One request through the engine (100 tokens in four chunks, six steps)
    with its logits and the program's choices, for the controls."""
    cfg, params = model
    moe.PROBE = probe = Probe()
    try:
        eng = _engine(cfg, params)
        req = _req("c", tokens_of(100, 21), 6)
        logits = _drive(eng, [req], probe)
    finally:
        moe.PROBE = Probe()
    return req, logits["c"], probe


# the controls that MUST fail the tight comparison, one case each
CONTROLS = {
    "the_attention_gate_a_head_not_a_channel": dict(gate_per_head=True),
    "rope_over_all_of_a_head": dict(rope_all=True),
    "qk_norm_dropped": dict(qk_norm=False),
    "the_delta_gate_as_2_sigmoid": dict(delta_gate="2sigmoid"),
    "the_delta_norm_zero_centred": dict(delta_norm_offset=True),
    "the_shared_gate_dropped": dict(shared_gate=False),
    "weights_not_renormalised_over_the_chosen": dict(renormalize=False),
    "one_held_expert_dropped": dict(drop_expert=1),
    "a_bfloat16_state": dict(state_bf16=True),
    "a_state_zeroed_at_a_chunk_boundary": dict(zero_state_at=96),
}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_each_control_fails_the_tight_comparison(control, model, finished):
    """The reference with ONE fault, on the program's own choices, at every
    compared step: over the limit, which is a hundred times the engine's
    own error."""
    _, params = model
    req, logits, probe = finished
    sound, _, _, want = compare(params, probe, req, logits)
    assert sound < TOL
    seq = req.prompt_tokens + req.output_tokens
    rows = [len(req.prompt_tokens) + n - 1 for n in sorted(logits)]
    bad = np.asarray(reference.forward(
        params, HF, jnp.asarray(seq), rows=rows,
        choices=probe.choices(seq, L, K), **CONTROLS[control]))
    least = min(_rel(b, w) for b, w in zip(bad, want))
    assert least > FAULT_LIMIT, (control, least)
    assert sound < least / 100


def test_bfloat16_products_fail_the_tight_tolerance(model, probe):
    """The control in lower precision: the same engine with bfloat16
    weights and activations against the float32 reference on ITS choices
    misses the tight tolerance by two orders (and the loose one is what lets
    its flipped near-ties pass: they lie within bfloat16's rounding of the
    reference's fourth, not within float32's)."""
    cfg, params = model
    low = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16)
        if a.dtype == jnp.float32 and a.ndim > 2 else a, params)
    eng = _engine(dataclasses.replace(cfg, dtype="bfloat16"), low)
    req = _req("b", tokens_of(100, 21), 6)
    logits = _drive(eng, [req], probe)
    worst, differ, miss, _ = compare(low, probe, req, logits["b"])
    assert worst > 100 * TOL, worst
    assert miss < 0.05, (differ, miss)


# ---- held experts -------------------------------------------------------------


def test_the_two_shares_add_up_to_the_uncut_layer(model):
    """THE SHARE TEST.  One expert layer of the small model, 16 routed
    experts, ranks [0, 8) and [8, 16): each routes over all 16 at the
    published top-4 and computes its own experts' part (``moe_ffn`` under
    ``held_experts``); the two parts plus the GATED shared expert counted
    ONCE are the uncut reference layer.  float32, a sum in another order:
    1e-5 of outputs of size 0.05."""
    from helix_tpu.models.moe import moe_ffn

    whole_cfg = dataclasses.replace(tiny(), held_experts=None)
    E, X, F, T = 64, 16, 32, 50
    ks = jax.random.split(jax.random.PRNGKey(4), 9)
    x = jax.random.normal(ks[0], (1, T, E))
    w_r = jax.random.normal(ks[1], (E, X)) * 0.3
    experts = {n: {"weight": jax.random.normal(k, shp) * 0.05}
               for n, k, shp in (("w_gate", ks[2], (X, E, F)),
                                 ("w_up", ks[3], (X, E, F)),
                                 ("w_down", ks[4], (X, F, E)))}
    shared = {n: {"weight": jax.random.normal(k, shp)[None] * 0.05}
              for n, k, shp in (("w_gate", ks[5], (E, F)),
                                ("w_up", ks[6], (E, F)),
                                ("w_down", ks[7], (F, E)))}
    w_sg = jax.random.normal(ks[8], (1, E, 1)) * 0.3
    lp = {"router": {"weight": w_r[None]},
          "experts": jax.tree.map(lambda a: a[None], experts),
          "shared": shared, "shared_gate": {"weight": w_sg}}
    hf = dict(HF, held_experts=None)
    with jax.default_matmul_precision("highest"):
        parts = []
        for lo in (0, 8):
            cfg = dataclasses.replace(whole_cfg, held_experts=(lo, lo + 8))
            mine = jax.tree.map(lambda a: a[lo:lo + 8], experts)
            part, stats = moe_ffn(x, w_r, mine, cfg, jax.nn.silu,
                                  backend="reference", return_stats=True)
            assert int(stats[1]) + int(stats[5]) == T * 4
            assert float(stats[3]) <= 8            # experts touched: held
            parts.append(part[0])
        uncut, _ = reference.expert_layer(x[0], lp, 0, hf, {})
        gated_shared, _ = reference.expert_layer(
            x[0], lp, 0, hf, {"drop_expert": "all"})
        shares = [reference.expert_layer(
            x[0], dict(lp, experts=jax.tree.map(
                lambda a: a[:, lo:lo + 8], lp["experts"])), 0,
            dict(HF, held_experts=[lo, lo + 8]), {"shared": False})[0]
            for lo in (0, 8)]
    assert float(jnp.abs(sum(parts) + gated_shared - uncut).max()) < 1e-5
    assert float(jnp.abs(sum(shares) + gated_shared - uncut).max()) < 1e-5
    for part, share in zip(parts, shares):
        assert float(jnp.abs(part - share).max()) < 1e-5
    # the gate is on the shared expert: counted twice, or ungated, it is seen
    ungated, _ = reference.expert_layer(
        x[0], lp, 0, hf, {"drop_expert": "all", "shared_gate": False})
    assert float(jnp.abs(ungated - gated_shared).max()) > 1e-3
    assert float(jnp.abs(uncut).max()) > 1e-2
    assert all(float(jnp.abs(p + gated_shared - uncut).max()) > 1e-3
               for p in parts)


# ---- the q/k norms' gains, a family -------------------------------------------


def _zero_q(params, cfg, how):
    """``params`` with every attention layer's query zeroed: through its
    projection (``wq`` = 0), or through the q norm's stored gain (``how`` the
    value that makes the gain 0 as the family reads it)."""
    out = jax.tree.map(lambda a: a, params)
    for group in cfg.layer_runs():
        for run in group.runs:
            if "q_norm" not in out[run.key]:
                continue
            lp = dict(out[run.key])
            if how == "wq":
                lp["wq"] = {**lp["wq"],
                            "weight": jnp.zeros_like(lp["wq"]["weight"])}
            else:
                lp["q_norm"] = {"weight": jnp.full_like(
                    lp["q_norm"]["weight"], how)}
            out[run.key] = lp
    return out


@pytest.mark.parametrize("family,hf,zero", [
    # plain gains: a stored 0 is a gain of 0
    ("qwen3", dict(
        model_type="qwen3", vocab_size=256, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=128, num_hidden_layers=2, rms_norm_eps=1e-6,
        rope_theta=1e6, max_position_embeddings=512), 0.0),
    ("lfm2_moe", dict(
        model_type="lfm2_moe", vocab_size=256, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=3,
        num_dense_layers=1, layer_types=["conv", "full_attention", "conv"],
        conv_L_cache=3, conv_bias=False, norm_eps=1e-5, rope_theta=1e6,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
        use_expert_bias=True, routed_scaling_factor=1,
        max_position_embeddings=512), 0.0),
    ("brumby", dict(
        model_type="brumby", vocab_size=256, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=128, num_hidden_layers=2, rms_norm_eps=1e-6,
        rope_theta=1e6, max_position_embeddings=512,
        tie_word_embeddings=False), 0.0),
    # zero-centred: a stored -1 is a gain of 0, a stored 0 a gain of 1
    ("qwen3_next", HF, -1.0),
])
def test_qk_norm_gains_are_read_as_the_family_stores_them(family, hf, zero):
    """``q_norm`` / ``k_norm`` take the family's norm offset: Qwen3, LFM2 and
    Brumby store the gain, Qwen3-Next its offset from 1.  A query zeroed
    through the norm's gain (as the family stores a gain of 0) gives the
    logits of a query zeroed through ``wq``; read the other way it does
    not."""
    cfg = dataclasses.replace(
        ModelConfig.from_hf_config(hf, name=f"tiny-{family}"),
        dtype="float32")
    assert cfg.qk_norm and cfg.norm_offset == (1.0 if zero else 0.0)
    params = init_params(cfg, jax.random.PRNGKey(3))
    toks, pos = jnp.asarray(tokens_of(24, 5))[None], jnp.arange(24)[None]

    def run(p):
        with jax.default_matmul_precision("highest"):
            return np.asarray(forward(p, cfg, toks, pos,
                                      attn_fn=prefill_attn_fn)[0])

    sound = run(params)
    by_wq = run(_zero_q(params, cfg, "wq"))
    by_gain = run(_zero_q(params, cfg, zero))
    other = run(_zero_q(params, cfg, zero + 1.0))
    assert np.abs(by_gain - by_wq).max() < 1e-5
    assert np.abs(sound - by_wq).max() > 1e-3
    assert np.abs(other - by_wq).max() > 1e-3


# ---- counters -----------------------------------------------------------------


def test_sixteen_decode_rows_are_counted_where_the_router_sent_them():
    """The series the kinds already report, at this shape, through the serving
    loop and the HTTP surface's collector: 16 decode rows at top-10 of 512
    give 160 assignments a layer, about half of them to the held [0, 256),
    and no more experts touched than are held; the flight record's fields."""
    import threading

    from helix_tpu.serving.engine_loop import EngineLoop
    from helix_tpu.serving.openai_api import OpenAIServer
    from helix_tpu.serving.registry import ModelRegistry, ServedModel
    from helix_tpu.serving.tokenizer import ByteTokenizer

    cfg = dataclasses.replace(
        ModelConfig.from_hf_config(dict(
            HF, num_experts=256, published_num_experts=512,
            held_experts=[0, 256], num_experts_per_tok=10,
            num_hidden_layers=4), name="tiny-qwen3-next-wide"),
        dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(5))
    eng = _engine(cfg, params, max_decode_batch=16, num_pages=64,
                  max_pages_per_seq=4)
    loop = EngineLoop(eng, "tiny-qwen3-next")    # never started: inline
    left = [16]
    done = threading.Event()

    def on(e):
        if e.finished:
            left[0] -= 1
            if not left[0]:
                done.set()

    for i in range(16):
        # (the first prompt is two chunks: the second walks pages)
        loop.submit(_req(f"d{i}", tokens_of(5 + i % 7 if i else 40, 40 + i),
                         12), on)
    for _ in range(400):
        if done.is_set():
            break
        assert loop._pass()
    assert done.is_set()
    eng._drain_moe_drops()
    # every (token, choice) of every layer is counted, here or away: a decode
    # row's 10 in each of 4 layers (16 rows: 160 a layer), a prompt token's
    prompt_tokens = 40 + sum(5 + i % 7 for i in range(1, 16))
    tokens = prompt_tokens + eng.mixer_counts["decode_rows"]
    held, away = eng.moe_routed_tokens, eng.moe_away_tokens
    assert held + away == tokens * 10 * 4
    assert 0.4 < held / (held + away) < 0.6      # about 80 of a step's 160
    assert 0 < eng.moe_experts_touched <= 256
    assert 0.0 < eng.moe_tile_fill_ratio <= 1.0
    records = loop.flight.snapshot()["recent"]
    assert records and all(
        r["deltanet_layers"] == 3 and r["attn_layers"] == 1
        and r["held_experts"] == 256 for r in records)
    # (the ring keeps the last steps: the chunks were counted in the first)
    assert all("deltanet_chunks" in r for r in records)
    assert eng.mixer_counts["chunks"] == 17 * 3
    registry = ModelRegistry()
    registry.register(ServedModel(
        name="tiny-qwen3-next", loop=loop, tokenizer=ByteTokenizer(),
        context_length=64))
    text = OpenAIServer(registry).obs.render()

    def value(series, label=""):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(series) and label in ln)
        return float(line.rsplit(" ", 1)[1])

    assert value("helix_deltanet_rows_total{", 'kind="chunk"') == 17
    assert value("helix_deltanet_rows_total{", 'kind="decode"') == (
        eng.mixer_counts["decode_rows"])
    assert value("helix_deltanet_chunks_total{") == 17 * 3
    held = value("helix_moe_held_tokens_total{")
    away = value("helix_moe_away_tokens_total{")
    assert held == eng.moe_routed_tokens > 0
    assert 0.7 < away / held < 1.4
    assert 0 < value("helix_moe_experts_touched{") <= 256
    assert 0 < value("helix_moe_tile_fill_ratio{") <= 1
    assert value("helix_attn_page_bytes_read_total{") > 0
    assert value("helix_attn_query_blocks_total{") > 0


# ---- refusals, scopes ---------------------------------------------------------

REFUSED_SETTINGS = {
    "int8_kv": (dict(kv_cache_dtype="int8"), "kv_cache_dtype int8"),
    "adapters": (dict(adapter_pool_slots=2), "adapter_pool_slots"),
    "speculation": (dict(enable_spec_decode=True), "enable_spec_decode"),
    "host_tier": (dict(host_pool_bytes=1 << 20), "host_pool_bytes"),
    "prefix_cache": (dict(enable_prefix_cache=True), "enable_prefix_cache"),
}


@pytest.mark.parametrize("name", sorted(REFUSED_SETTINGS))
def test_what_cannot_carry_the_state_is_refused_by_name(model, name):
    """``_REFUSALS`` unchanged in what they refuse: beside GQA pages the
    delta rule's state is carried by no more than beside latent ones."""
    cfg, params = model
    kw, setting = REFUSED_SETTINGS[name]
    with pytest.raises(UnsupportedForModel, match=setting):
        _engine(cfg, params, **kw)


def test_a_mesh_is_refused_by_name(model):
    from helix_tpu.engine.engine import refuse_unsupported

    cfg, _ = model

    class TwoDevices:
        devices = np.zeros((2,))

    with pytest.raises(UnsupportedForModel, match="gated delta rule"):
        refuse_unsupported(cfg, EngineConfig(enable_prefix_cache=False),
                           TwoDevices())


SCOPES = ("deltanet.in_proj", "deltanet.conv", "deltanet.mix",
          "deltanet.out_proj", "attn.qkv", "attn.kernel", "attn.gate",
          "attn.out", "moe.router", "moe.experts", "moe.shared",
          "moe.shared_gate")


@pytest.fixture(scope="module")
def lowered_text(model):
    import joint_pass

    cfg, params = model
    eng = _engine(cfg, params)
    eng.add_request(_req("d", tokens_of(7, 3), 40, seed=11))
    eng.step()
    eng.step()
    fn, args = joint_pass.step_program(eng, 32, 1, True)
    return fn.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_step_carries_the_named_scope(lowered_text, scope):
    import re

    assert re.search(rf"[/\"]{re.escape(scope)}[/\"]", lowered_text), scope
