"""The new configuration's yardstick (ISSUE 28): the operations-and-bytes
functions of ``benchmark/lib/model_bytes_mla_moe.py`` against hand counts
and against what the program allocates, the configuration file against the
published config, and the plain reference beside it against the program's
forward pass at a small size on the CPU."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import model_bytes_mla_moe as mb  # noqa: E402
from benchmark.lib import peaks  # noqa: E402

NAME = "deepseek-v2-lite-int8"


def config():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


PUBLISHED = {
    "hidden_size": 2048, "num_attention_heads": 16, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "n_routed_experts": 64, "moe_intermediate_size": 1408,
    "num_experts_per_tok": 6, "n_shared_experts": 2,
    "intermediate_size": 10944, "vocab_size": 102400,
    "first_k_dense_replace": 1, "q_lora_rank": None,
}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_width_is_as_published(key):
    assert config()[key] == PUBLISHED[key]


def test_the_cut_is_depth_only_and_stated():
    cfg = config()
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 17
    assert cfg["published_num_hidden_layers"] == 27
    assert cfg["assumed"] == [] and "pipeline stage" in cfg["deployment"]
    assert cfg["rope_scaling"]["factor"] == 40
    srv = cfg["serving"]
    assert (srv["num_pages"], srv["max_decode_batch"], srv["page_size"],
            srv["max_prefill_len"]) == (10240, 64, 16, 512)
    profile = open(os.path.join(ROOT, cfg["profile"])).read()
    for size in ("num_layers: 17", "kv_lora_rank: 512", "num_experts: 64",
                 "moe_intermediate_size: 1408", "max_pages_per_seq: 160",
                 "num_pages: 10240", "max_decode_batch: 64"):
        assert size in profile, size


def test_catalog_keys_are_copied_whole():
    """Against the guide's catalog row, where the sandbox has it."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "DeepSeek-V2-Lite")
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k != "num_hidden_layers":
            assert cfg[k] == v, k


def test_weight_bytes_by_part_against_the_issues_hand_count():
    p = mb.weight_bytes_by_part(config(), "int8")
    # one routed expert 3 x 2048 x 1408 = 8.65M parameters, 64 x 16 of them
    assert p["one_expert"] == 3 * 2048 * 1408 + (2 * 1408 + 2048) * 4
    assert abs(p["routed_experts"] / 1e9 - 8.86) < 0.02
    assert abs(p["shared_experts"] / 1e9 - 0.28) < 0.01
    assert abs(p["attention"] / 1e9 - 0.23) < 0.01
    assert abs(p["dense_mlp"] / 1e9 - 0.07) < 0.005
    assert abs((p["embedding"] + p["head"]) / 1e9 - 0.42) < 0.005
    assert abs(p["total"] / 1e9 - 9.87) < 0.03
    whole = dict(config(), num_hidden_layers=27)
    assert abs(mb.weight_bytes_by_part(whole)["total"] / 1e9 - 15.74) < 0.05


def test_weight_and_page_bytes_are_what_the_program_allocates():
    import jax

    from helix_tpu.engine.kv_cache import CacheConfig
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params

    cfg = config()
    model = ModelConfig.from_hf_config(cfg)
    assert model.num_layers == 17 and model.is_mla
    tree = jax.eval_shape(
        lambda: init_params(model, jax.random.PRNGKey(0), int8=True))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(tree))
    assert held == mb.weight_bytes_by_part(cfg, "int8")["total"]
    cc = CacheConfig(num_pages=10240, page_size=16)
    assert cc.page_bytes(model) == mb.page_bytes(cfg, 16) == 348160
    assert mb.latent_bytes_per_token(cfg, padded=False) == 17 * 576 * 2


def test_decode_step_bytes_follow_the_experts_touched():
    cfg = config()
    one = mb.weight_bytes_by_part(cfg)["one_expert"]
    a = mb.decode_step_bytes(cfg, 80_000, 64, 64)
    b = mb.decode_step_bytes(cfg, 80_000, 40, 64)
    assert a - b == 16 * 24 * one
    c = mb.decode_step_bytes(cfg, 81_000, 64, 64)
    assert c - a == 1000 * 17 * 576 * 2
    assert mb.decode_step_bytes(cfg, 81_000, 64, 64, padded=True) - a == (
        81_000 * 17 * 640 * 2 - 80_000 * 17 * 576 * 2)
    # the ISSUE's floor: 9.65 GB of weights and 1.6 GB of cache a step
    assert abs(a / 1e9 - (9.67 + 1.57)) < 0.05


def test_kernel_operations_and_bytes():
    cfg = config()
    ops, byt = mb.mla_kernel_call(cfg, [1], [1000])
    assert ops == 2 * 16 * 1001 * (512 + 64 + 512)
    assert byt == 1000 * 576 * 2 + 576 * 2 + 16 * 576 * 2 + 16 * 512 * 2
    ops2, _ = mb.mla_kernel_call(cfg, [512], [0])
    assert ops2 == 2 * 16 * (512 * 513 / 2) * 1088
    gops, gbyt = mb.grouped_expert_product(cfg, 64 * 6, 64)
    assert gops == 3 * 2 * 384 * 2048 * 1408
    assert gbyt == 64 * mb.weight_bytes_by_part(cfg)["one_expert"] + (
        384 * (2 * 2048 + 3 * 1408) * 2)
    chip = peaks.chip_peaks("TPU v5 lite")
    share, bound = mb.roofline_share(gops, gbyt, 1e-3, chip)
    assert bound == "hbm" and share == pytest.approx(
        100 * gbyt / 819e9 / 1e-3)
    share, bound = mb.roofline_share(ops2 * 1000, 1, 1.0, chip)
    assert bound == "flops" and 0 < share < 100


def test_reference_beside_the_configuration():
    path = os.path.join(ROOT, "benchmark", "configs", NAME + ".reference.py")
    spec = importlib.util.spec_from_file_location("ref_deepseek", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.forward)
    assert mod.CONFIG["name"] == NAME and mod.CONFIG["hidden_size"] == 2048


@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
def test_reference_agrees_with_the_programs_forward(int8):
    """The configuration's geometry in miniature: a dense layer then
    expert layers, shared experts, top-3 of 8 unnormalised, latent
    attention with YaRN rope."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.reference_mla_moe_decoder import forward as reference
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import forward, init_params, prefill_attn_fn

    hf = dict(config(), hidden_size=96, num_attention_heads=6,
              num_key_value_heads=6, kv_lora_rank=48, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=24, n_routed_experts=8,
              num_experts_per_tok=3, moe_intermediate_size=40,
              intermediate_size=112, vocab_size=320, num_hidden_layers=4,
              max_position_embeddings=512)
    hf["rope_scaling"] = dict(hf["rope_scaling"],
                              original_max_position_embeddings=32)
    cfg = ModelConfig.from_hf_config(hf)
    import dataclasses

    cfg = dataclasses.replace(cfg, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(2), int8=int8)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 320, size=(1, 40)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, tokens, jnp.arange(40)[None],
                         attn_fn=prefill_attn_fn)
    want = reference(params, hf, tokens[0])
    # float32 both sides, the same mathematics by other routes (absorbed
    # against decompressed attention, a grouped product against a loop over
    # experts): rounding only.  bf16 anywhere would miss this by 1e-2.
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-5
