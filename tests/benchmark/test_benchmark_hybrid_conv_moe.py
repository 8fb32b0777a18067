"""The new configuration's yardstick (ISSUE 32): the operations-and-bytes
functions of ``benchmark/lib/model_bytes_hybrid_conv_moe.py`` against hand
counts and against what the program allocates, the configuration file
against the published config, and the plain reference beside it against the
program's forward pass at a small size on the CPU."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import model_bytes_hybrid_conv_moe as mb  # noqa: E402
from benchmark.lib import peaks  # noqa: E402

NAME = "lfm2-8b-a1b-int8"
CELL = "lfm2-8b-a1b.saturated-long"


def config():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


PUBLISHED = {
    "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 8,
    "intermediate_size": 7168, "moe_intermediate_size": 1792,
    "num_experts": 32, "num_experts_per_tok": 4, "num_dense_layers": 2,
    "num_hidden_layers": 24, "conv_L_cache": 3, "conv_bias": False,
    "vocab_size": 65536, "use_expert_bias": True, "norm_topk_prob": True,
    "rope_theta": 1000000, "model_type": "lfm2_moe",
}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_size_is_as_published(key):
    assert config()[key] == PUBLISHED[key]


def test_nothing_is_cut_and_what_is_assumed_is_stated():
    cfg = config()
    assert cfg["reduced"] == []
    assert cfg["layer_types"].count("conv") == 18
    assert cfg["layer_types"].count("full_attention") == 6
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assumed = " ".join(cfg["assumed"])
    for word in ("tie_embedding", "head width 64", "taps", "expert_bias",
                 "unit RMS"):
        assert word in assumed, word
    assert "whole model" in cfg["deployment"]
    srv = cfg["serving"]
    assert (srv["num_pages"], srv["max_decode_batch"], srv["page_size"],
            srv["max_prefill_len"], srv["max_context_tokens"]) == (
        10240, 64, 16, 512, 2560)
    assert srv["kv_bytes_per_token"] == mb.kv_bytes_per_token(cfg) == 12288
    assert srv["state_bytes_per_slot"] == mb.state_bytes_per_slot(cfg)
    profile = open(os.path.join(ROOT, cfg["profile"])).read()
    for size in ("num_layers: 24", "num_experts: 32", "head_dim: 64",
                 "moe_intermediate_size: 1792", "max_pages_per_seq: 160",
                 "num_pages: 10240", "max_decode_batch: 64",
                 "moe_scoring: sigmoid", "conv_kernel: 3", "__SEED__"):
        assert size in profile, size


def test_catalog_keys_are_copied_whole():
    """Against the guide's catalog row, where the sandbox has it."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "LFM2-8B-A1B")
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        assert cfg[k] == v, k


def test_the_profile_builds_the_catalog_model():
    """``model_overrides`` restates the catalog entry: applied to it (as
    ``node_agent`` applies them) nothing changes, and the configuration
    file's Hugging Face keys give the same model."""
    import dataclasses

    import yaml

    from helix_tpu.models.common import LFM2_8B_A1B, ModelConfig

    cfg = config()
    with open(os.path.join(ROOT, cfg["profile"])) as f:
        prof = yaml.safe_load(f.read().replace("__SEED__", "7"))
    over = dict(prof["models"][0]["model_overrides"])
    over["layer_types"] = tuple(over["layer_types"])
    assert prof["models"][0]["name"] == cfg["model"] == LFM2_8B_A1B.name
    assert dataclasses.replace(LFM2_8B_A1B, **over) == LFM2_8B_A1B
    assert ModelConfig.from_hf_config(cfg, name=cfg["model"]) == LFM2_8B_A1B
    eng = prof["models"][0]["engine"]
    assert set(eng) == {"max_decode_batch", "page_size", "max_prefill_len",
                        "kv_cache_dtype", "num_pages", "max_pages_per_seq"}


def test_parameter_count_against_the_issues_hand_count():
    p = mb.parameter_count(config())
    assert p["conv_operators"] == 18 * (2048 * 6144 + 2048 * 2048 + 2048 * 3)
    assert abs(p["conv_operators"] / 1e6 - 302) < 0.5
    assert abs(p["attention_operators"] / 1e6 - 63) < 0.5
    assert p["dense_mlp"] == 2 * 3 * 2048 * 7168
    assert p["routed_experts"] == 22 * 32 * 3 * 2048 * 1792
    assert abs(p["routed_experts"] / 1e6 - 7751) < 0.5
    assert abs(p["embedding"] / 1e6 - 134) < 0.5
    assert abs(p["total"] / 1e9 - 8.34) < 0.005


def test_weight_page_and_state_bytes_are_what_the_program_allocates():
    import jax

    from helix_tpu.engine.kv_cache import CacheConfig
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params

    cfg = config()
    model = ModelConfig.from_hf_config(cfg)
    tree = jax.eval_shape(
        lambda: init_params(model, jax.random.PRNGKey(0), int8=True))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(tree))
    parts = mb.weight_bytes_by_part(cfg, "int8")
    assert held == parts["total"]
    assert abs(parts["total"] / 1e9 - 8.357) < 0.001
    assert parts["one_expert"] == 3 * 2048 * 1792 + (2 * 1792 + 2048) * 4
    cc = CacheConfig(num_pages=10240, page_size=16, state_slots=64)
    assert cc.page_bytes(model) == mb.page_bytes(cfg, 16) == 196608
    assert cc.state_bytes(model) == 64 * mb.state_bytes_per_slot(cfg)
    assert abs(cc.state_bytes(model) / 1e6 - 9.4) < 0.05
    assert abs(cc.total_bytes(model) / 1e9 - 2.023) < 0.001
    # the notes' bytes are these, within 2%
    notes = " ".join(cfg["notes"])
    for said, got in (("8.357 GB", parts["total"] / 1e9),
                      ("7.767 GB", parts["routed_experts"] / 1e9),
                      ("2.013 GB", 10240 * 196608 / 1e9),
                      ("9.44 MB", cc.state_bytes(model) / 1e6)):
        assert said in notes and abs(float(said.split()[0]) / got - 1) < 0.02


def test_decode_step_bytes_follow_experts_context_and_state():
    cfg = config()
    one = mb.weight_bytes_by_part(cfg)["one_expert"]
    a = mb.decode_step_bytes(cfg, 80_000, 32, 64)
    assert a - mb.decode_step_bytes(cfg, 80_000, 20, 64) == 12 * 22 * one
    assert mb.decode_step_bytes(cfg, 81_000, 32, 64) - a == 1000 * 12288
    assert mb.decode_step_bytes(cfg, 80_000, 32, 65) - a == (
        2048 + mb.state_bytes_per_slot(cfg))
    # the ISSUE's floor: 8.3 GB of weights and about 1 GB of cache a step
    assert abs(a / 1e9 - 9.35) < 0.02
    floor_ms = a / peaks.chip_peaks("TPU v5 lite")["hbm_bytes_per_s"] * 1e3
    assert 11.0 < floor_ms < 11.8


def test_kernel_calls_count_what_the_algorithm_needs():
    cfg = config()
    # 64 decode rows over 1,250 tokens of history each
    ops, by = mb.paged_kernel_call(cfg, [1] * 64, [1250] * 64)
    assert ops == 2 * 32 * 64 * 1251 * 2 * 64
    assert by == 64 * 1250 * 2 * 8 * 64 * 2 + 64 * 2 * 8 * 64 * 2 + (
        2 * 64 * 32 * 64 * 2)
    share, bound = mb.roofline_share(ops, by, 1e-3, peaks.chip_peaks("TPU v5 lite"))
    assert bound == "hbm" and 0 < share < 100
    # a 512-token chunk over 512 tokens of history is bound by operations
    ops_c, by_c = mb.paged_kernel_call(cfg, [512], [512])
    assert ops_c == 2 * 32 * (512 * 512 + 512 * 513 / 2) * 128
    ops_g, by_g = mb.grouped_expert_product(cfg, 256, 32)
    assert ops_g == 3 * 2 * 256 * 2048 * 1792
    one = mb.weight_bytes_by_part(cfg)["one_expert"]
    assert by_g == 32 * one + 256 * (2 * 2048 + 3 * 1792) * 2
    assert mb.roofline_share(ops_g, by_g, 1e-3, peaks.chip_peaks("TPU v5 lite"))[1] == (
        "hbm")


def test_the_reference_beside_the_configuration_is_the_programs_forward():
    """Loaded by path as the harness loads it, at a small size: the
    program's forward pass and the plain reference agree in float32."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import forward, init_params, prefill_attn_fn

    path = os.path.join(ROOT, config()["reference"])
    spec = importlib.util.spec_from_file_location("lfm2_reference", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    assert ref.CONFIG["name"] == NAME
    small = dict(
        ref.CONFIG, vocab_size=128, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=96, moe_intermediate_size=32,
        num_hidden_layers=9, num_experts=8, num_experts_per_tok=2,
        layer_types=["conv", "conv", "full_attention", "conv", "conv",
                     "full_attention", "conv", "conv", "conv"])
    cfg = dataclasses.replace(
        ModelConfig.from_hf_config(small), dtype="float32")
    assert cfg.head_dim == 16 and cfg.kv_head_pack == 1
    params = init_params(cfg, jax.random.PRNGKey(1))
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 128, 33))
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, toks[None], jnp.arange(33)[None],
                         attn_fn=prefill_attn_fn)
    want = ref.forward(params, small, toks)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-4


def test_the_cell_is_listed_where_the_issue_says_and_nowhere_else():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "saturated-long", 1)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {
        "sched.slot_occupancy", "device.idle_share.saturated",
        "kernel.attn_share.saturated", "kernel.grouped_mm_share",
        "loop.host_build_ms.saturated", "loop.admit_ms.saturated",
        "loop.prefill_sync_ms.saturated", "loop.dispatch_ms.saturated",
        "loop.fetch_ms.saturated", "loop.reconcile_ms.saturated",
        "loop.emit_ms.saturated", "loop.deliver_ms.saturated",
        "loop.emit_queue_wait_ms.saturated", "step.chunk_ms.conv",
        "loop.state_snapshot_ms.saturated"}
    assert {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", ())} == {
        "tokens_per_s", "tpot_p95_ms.saturated"}
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "step.chunk_ms.conv.json")) as f:
        assert "whole_op" not in json.load(f)
