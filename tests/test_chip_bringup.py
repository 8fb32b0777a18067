"""CPU tests of what the one-chip bring-up rests on: a load path that never
holds the float model beside the int8 one, the one compile-cache rule, the
device tables, and the no-fallback rules.  (What the chip's compiler accepts
is ``tests/test_tpu_compile.py``; what runs on the chip is ``chip_smoke.py``.)
"""

import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helix_tpu.models.common import CATALOG, ModelConfig
from helix_tpu.models.llama import init_params
from helix_tpu.ops.quant import quantize_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMALL = dict(   # Qwen2-7B's shape family, cut to CPU size
    num_layers=3, hidden_size=128, num_heads=4, num_kv_heads=2,
    head_dim=32, intermediate_size=256, vocab_size=512,
)


def _float_bytes(arrays):
    return sum(
        a.nbytes for a in arrays if jnp.issubdtype(a.dtype, jnp.floating)
    )


def _nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# the seeded int8 tree (catalog branch)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "overrides",
    [
        dict(attention_bias=True),
        dict(num_experts=4, num_experts_per_tok=2),
        dict(qk_norm=True, tie_word_embeddings=True),
    ],
    ids=["qwen2-like", "moe", "qknorm-tied"],
)
def test_seeded_int8_tree_is_what_quantize_params_gives(overrides):
    cfg = ModelConfig.tiny(**overrides)
    want = jax.eval_shape(
        lambda: quantize_params(init_params(cfg, jax.random.PRNGKey(0)))
    )
    got = init_params(cfg, jax.random.PRNGKey(0), int8=True)
    describe = lambda t: jax.tree.map(lambda x: (x.shape, str(x.dtype)), t)
    assert describe(got) == describe(want)
    codes = got["layers"]["wq"]["weight"]
    assert codes.dtype == jnp.int8 and int(jnp.abs(codes).max()) == 127


def test_seeded_int8_tree_depends_on_the_seed_alone():
    cfg = ModelConfig.tiny(attention_bias=True)
    a = init_params(cfg, jax.random.PRNGKey(7), int8=True)
    b = init_params(cfg, jax.random.PRNGKey(7), int8=True)
    c = init_params(cfg, jax.random.PRNGKey(8), int8=True)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["layers"]["w_up"]["weight"]
                     == c["layers"]["w_up"]["weight"]).all())


def test_seeded_int8_never_draws_a_stacked_tensor_in_float():
    """By shapes: no value inside the per-tensor jit has the stacked
    ``[L, in, out]`` shape in a float dtype — the draw is layer by layer."""
    from helix_tpu.models.llama import _seeded_int8

    shape = (5, 64, 96)
    jaxpr = jax.make_jaxpr(
        lambda k: _seeded_int8(k, shape, jnp.bfloat16, 0.02, False, None)
    )(jax.random.PRNGKey(0))
    seen = []

    def walk(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                seen.append((tuple(v.aval.shape), v.aval.dtype))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert (shape, jnp.dtype(jnp.int8)) in seen       # the walk went inside
    assert not [
        s for s, dt in seen
        if s == shape and jnp.issubdtype(dt, jnp.floating)
    ]


def test_build_served_model_int8_never_holds_the_float_model(monkeypatch):
    """serve-node's catalog branch: while ``_build_served_model`` builds a
    small int8 Qwen2, what is live on the device stays under the bf16 size
    of the model, and no float matmul weight is ever live."""
    import helix_tpu.models.llama as llama
    from helix_tpu.control.node_agent import _build_served_model
    from helix_tpu.control.profile import ProfileModel

    cfg = dataclasses.replace(CATALOG["Qwen/Qwen2-7B-Instruct"], **SMALL)
    bf16_bytes = _nbytes(
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    )
    # other tests of this worker leave arrays behind: count only ours
    before = {id(a) for a in jax.live_arrays()}
    peaks, float_peaks = [], []
    real = llama._seeded_int8

    def watched(*a, **kw):
        out = real(*a, **kw)
        jax.block_until_ready(out)
        live = [x for x in jax.live_arrays() if id(x) not in before]
        peaks.append(sum(x.nbytes for x in live))
        float_peaks.append(max(
            [x.nbytes for x in live
             if jnp.issubdtype(x.dtype, jnp.floating) and x.ndim >= 2
             and x.nbytes > 64 * 1024] or [0]
        ))
        return out

    monkeypatch.setattr(llama, "_seeded_int8", watched)
    pm = ProfileModel.from_dict({
        "name": "Qwen/Qwen2-7B-Instruct",
        "quantization": "int8",
        "seed": 3,
        "model_overrides": SMALL,
        "engine": {"max_decode_batch": 2, "page_size": 4, "num_pages": 32,
                   "max_pages_per_seq": 8, "max_prefill_len": 16,
                   "attn_backend": "reference"},
    })
    served = _build_served_model(pm)
    try:
        params = served.loop.engine.params
        assert len(peaks) == 9          # embed, 7 stacked, lm_head
        assert max(peaks) < bf16_bytes
        # scales are the only float arrays of any size: [L, 1, out] rows
        assert max(float_peaks) < bf16_bytes / 20
        assert params["layers"]["w_gate"]["weight"].dtype == jnp.int8
        assert params["layers"]["wq"]["bias"].dtype == jnp.bfloat16
        assert _nbytes(params) < 0.6 * bf16_bytes
        want = init_params(cfg, jax.random.PRNGKey(3), int8=True)
        assert bool((params["embed"]["weight"]
                     == want["embed"]["weight"]).all())
    finally:
        served.loop.stop()


# ---------------------------------------------------------------------------
# the checkpoint branch: quantize on the way to the device
# ---------------------------------------------------------------------------


def _write_hf_checkpoint(path, cfg, params):
    """A Llama-layout safetensors checkpoint of an ``init_params`` tree."""
    from safetensors.numpy import save_file

    f32 = lambda x: np.asarray(x, np.float32)
    t = {
        "model.embed_tokens.weight": f32(params["embed"]["weight"]),
        "model.norm.weight": f32(params["final_norm"]["weight"]),
        "lm_head.weight": f32(params["lm_head"]["weight"]).T.copy(),
    }
    names = {
        "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
        "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
        "w_gate": "mlp.gate_proj", "w_up": "mlp.up_proj",
        "w_down": "mlp.down_proj",
    }
    lay = params["layers"]
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        t[p + "input_layernorm.weight"] = f32(lay["attn_norm"]["weight"][i])
        t[p + "post_attention_layernorm.weight"] = f32(
            lay["mlp_norm"]["weight"][i])
        for ours, theirs in names.items():
            t[p + theirs + ".weight"] = f32(lay[ours]["weight"][i]).T.copy()
    os.makedirs(path, exist_ok=True)
    save_file(t, os.path.join(path, "model.safetensors"))
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump({
            "architectures": ["LlamaForCausalLM"], "model_type": "llama",
            "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_norm_eps,
            "max_position_embeddings": cfg.max_position_embeddings,
            "tie_word_embeddings": False, "torch_dtype": "float32",
        }, f)


def test_checkpoint_loads_as_int8_one_tensor_at_a_time(tmp_path, monkeypatch):
    import helix_tpu.ops.quant as quant
    from helix_tpu.models.loader import load_params

    cfg = ModelConfig.tiny(dtype="float32")
    _write_hf_checkpoint(
        str(tmp_path), cfg, init_params(cfg, jax.random.PRNGKey(1))
    )
    _, floats = load_params(str(tmp_path))
    want = jax.jit(quantize_params)(floats)
    largest = max(x.nbytes for x in jax.tree.leaves(floats))
    del floats

    before = {id(a) for a in jax.live_arrays()}
    live_float = []
    real = quant._map_matmul_weights

    def watched(tree, quantize, other, aux=None, path=()):
        def q(v, a, embed):
            out = quantize(v, a, embed)
            jax.block_until_ready(out)
            # one source-dtype tensor at most, beside the scales so far
            live_float.append(_float_bytes(
                x for x in jax.live_arrays() if id(x) not in before))
            return out

        return real(tree, q, other, aux, path)

    monkeypatch.setattr(quant, "_map_matmul_weights", watched)
    _, got = load_params(str(tmp_path), quantize=True)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    same = jax.tree.map(
        lambda x, y: x.dtype == y.dtype and bool((x == y).all()), got, want
    )
    assert all(jax.tree.leaves(same))
    assert live_float and max(live_float) < 2 * largest


# ---------------------------------------------------------------------------
# the one compile-cache rule
# ---------------------------------------------------------------------------


def _fake_devices(platform, kind):
    dev = types.SimpleNamespace(
        platform=platform, device_kind=kind, id=0, process_index=0,
        coords=(0, 0, 0), memory_stats=lambda: None,
    )
    return lambda *a, **k: [dev]


@pytest.fixture
def cache_updates(monkeypatch):
    """What the helper would set, without touching this process' config."""
    seen = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: seen.__setitem__(k, v)
    )
    return seen


def test_cache_rule_obeys_the_environment(monkeypatch, cache_updates):
    from helix_tpu.device.compile_cache import configure_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu", "TPU v5 lite"))
    assert configure_compile_cache() == "/somewhere/else"
    assert cache_updates == {}      # nothing set in code


def test_cache_rule_defaults_to_the_checkout(monkeypatch, cache_updates):
    from helix_tpu.device.compile_cache import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu", "TPU v5 lite"))
    want = os.path.join(REPO, ".jax_cache")
    assert configure_compile_cache() == want
    assert cache_updates == {"jax_compilation_cache_dir": want}
    assert configure_compile_cache() == want     # fixed: no pid, no time


def test_cache_rule_keeps_no_cache_on_a_cpu(monkeypatch, cache_updates):
    from helix_tpu.device.compile_cache import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert configure_compile_cache() == ""
    assert cache_updates == {}


# A word the tree may hold in the named files alone.  The last two are the
# pre-chip instrument PR 30 retired (its script, its knobs): benchmark/run.py
# is the one instrument, and no module or tool names the old one as a reader.
@pytest.mark.parametrize(
    "word, roots, scripts, only_in",
    [
        ("jax_compilation_cache_dir", ("helix_tpu", "tools", "tests"),
         ("chip_smoke.py", "__graft_entry__.py"),
         ["helix_tpu/device/compile_cache.py"]),
        ("bench.py", ("helix_tpu", "tools"), (), []),
        ("HELIX_BENCH_", ("helix_tpu", "tools"), (), []),
    ],
    ids=["cache-directory", "retired-script", "retired-knobs"],
)
def test_a_word_stays_where_it_belongs(word, roots, scripts, only_in):
    hits = []
    for root in roots:
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            hits += [
                os.path.join(dirpath, f) for f in files
                if f.endswith(".py") and f != os.path.basename(__file__)
                and word in open(
                    os.path.join(dirpath, f), encoding="utf-8").read()
            ]
    hits += [f for f in scripts if word in open(os.path.join(REPO, f)).read()]
    assert [os.path.relpath(h, REPO) for h in hits] == only_in


def test_the_cli_has_no_bench_command(capsys):
    from helix_tpu import cli

    with pytest.raises(SystemExit) as exc:
        cli.main(["bench"])
    assert exc.value.code == 2                     # argparse's own refusal
    assert "invalid choice: 'bench'" in capsys.readouterr().err
    assert not os.path.exists(os.path.join(REPO, "bench.py"))


# ---------------------------------------------------------------------------
# device tables and the no-fallback rules
# ---------------------------------------------------------------------------


def test_detect_maps_the_chips_device_kind_to_v5e():
    from helix_tpu.device.detect import detect_accelerators

    (acc,) = detect_accelerators(_fake_devices("tpu", "TPU v5 lite")())
    assert (acc.vendor, acc.arch, acc.device_kind) == (
        "tpu", "v5e", "TPU v5 lite")
    assert acc.total_memory_bytes == 16 * 1024**3   # datasheet fallback


def test_peaks_table_knows_v5e_and_refuses_to_guess():
    from helix_tpu.device.peaks import UnknownDeviceKind, peak_flops

    assert peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(UnknownDeviceKind, match="TPU v9"):
        peak_flops("TPU v9")


def test_mfu_denominator_is_the_table_or_the_override(monkeypatch):
    from helix_tpu.device.peaks import UnknownDeviceKind
    from helix_tpu.serving.openai_api import OpenAIServer

    monkeypatch.delenv("HELIX_PEAK_FLOPS", raising=False)
    assert OpenAIServer._peak_flops() == 0.0            # CPU: no device
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu", "TPU v5 lite"))
    assert OpenAIServer._peak_flops() == 197e12
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu", "TPU v9"))
    with pytest.raises(UnknownDeviceKind):
        OpenAIServer._peak_flops()
    monkeypatch.setenv("HELIX_PEAK_FLOPS", "1e15")
    assert OpenAIServer._peak_flops() == 1e15


@pytest.mark.parametrize(
    "platform,asked,want",
    [("cpu", None, "reference"), ("tpu", None, "pallas"),
     ("tpu", "reference", "reference"), ("cpu", "pallas", "pallas"),
     ("gpu", None, RuntimeError)],
)
def test_attention_backend_is_resolved_not_guessed(monkeypatch, platform,
                                                   asked, want):
    from helix_tpu.ops.attention import resolve_backend

    monkeypatch.setattr(jax, "devices", _fake_devices(platform, "x"))
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="gpu"):
            resolve_backend(asked)
    else:
        assert resolve_backend(asked) == want


def test_engine_resolves_and_logs_its_backend_once(caplog):
    from helix_tpu.engine.engine import Engine, EngineConfig

    cfg = ModelConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    with caplog.at_level("INFO", logger="helix_tpu.engine.engine"):
        eng = Engine(cfg, params, EngineConfig(
            max_decode_batch=2, page_size=4, num_pages=16,
            max_pages_per_seq=4, max_prefill_len=8))
    assert eng._backend == "reference"      # a CPU backend, by name
    lines = [r.getMessage() for r in caplog.records
             if "attention backend" in r.getMessage()]
    assert len(lines) == 1
    assert "reference" in lines[0] and "platform cpu" in lines[0]


def test_engine_refuses_head_width_96_on_the_kernel_path():
    from helix_tpu.engine.engine import Engine, EngineConfig
    from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry

    cfg = ModelConfig.tiny(num_heads=4, num_kv_heads=4, head_dim=96,
                           hidden_size=384)
    with pytest.raises(UnsupportedKernelGeometry, match="width 96"):
        Engine(cfg, {}, EngineConfig(
            max_decode_batch=2, page_size=4, num_pages=16,
            max_pages_per_seq=4, max_prefill_len=8, attn_backend="pallas"))


def test_next_token_logits_are_what_the_next_step_samples_from():
    from helix_tpu.engine.engine import Engine, EngineConfig, Request
    from helix_tpu.engine.sampling import SamplingParams

    cfg = ModelConfig.tiny(dtype="float32")
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)), EngineConfig(
        max_decode_batch=2, page_size=4, num_pages=32, max_pages_per_seq=8,
        max_prefill_len=16, decode_steps_per_sync=1))
    req = Request(id="r", prompt_tokens=[5, 6, 7, 8, 9],
                  sampling=SamplingParams(temperature=0.0, max_tokens=6))
    eng.add_request(req)
    while not req.output_tokens:
        eng.step()
    for _ in range(3):
        n = len(req.output_tokens)
        logits = np.asarray(eng.next_token_logits())
        again = np.asarray(eng.next_token_logits())     # nothing advanced
        assert logits.shape == (2, cfg.vocab_size) and (logits == again).all()
        eng.step()
        slot = next(i for i, r in enumerate(eng.slots) if r is req)
        assert req.output_tokens[n] == int(logits[slot].argmax())


def test_scale_pages_pack_lane_dense_and_back():
    from helix_tpu.ops.quant import pack_scale_pages, unpack_scale_pages

    x = jnp.arange(2 * 3 * 16 * 4, dtype=jnp.float32).reshape(2, 3, 16, 4)
    rows = pack_scale_pages(x)
    assert rows.shape == (2, 3, 64)
    # head-major inside a page: head k's 16 tokens are contiguous lanes
    assert bool((rows[0, 0, 16:32] == x[0, 0, :, 1]).all())
    assert bool((unpack_scale_pages(rows, 16) == x).all())


def test_int8_page_bytes_count_the_lane_padding():
    from helix_tpu.engine.kv_cache import CacheConfig

    qwen, llama = (CATALOG[n] for n in (
        "Qwen/Qwen2-7B-Instruct", "meta-llama/Meta-Llama-3-8B-Instruct"))
    for model, rows in ((qwen, 128), (llama, 128)):
        c = CacheConfig(num_pages=1, page_size=16, dtype="int8")
        codes = 2 * model.num_layers * 16 * model.num_kv_heads * 128
        assert c.page_bytes(model) == codes + 2 * model.num_layers * rows * 4


def test_the_smoke_profile_is_qwen2_7b_at_published_widths():
    from helix_tpu.control.profile import ServingProfile

    with open(os.path.join(REPO, "profiles", "v5e1-qwen2-7b.yaml")) as f:
        prof = ServingProfile.from_yaml(f.read())
    assert prof.validate() == []
    (pm,) = prof.models
    cfg = CATALOG[pm.name]
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size,
            cfg.vocab_size, cfg.attention_bias) == (
        28, 3584, 28, 4, 128, 18944, 152064, True)
    assert pm.checkpoint is None and pm.quantization == "int8"
    assert pm.mesh.num_devices == 1 and not pm.model_overrides
    assert pm.engine["max_decode_batch"] == 32
    assert pm.engine["kv_cache_dtype"] == "auto"
