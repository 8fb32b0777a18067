"""The new configuration's yardstick (ISSUE 41): the operations-and-bytes
functions of ``benchmark/lib/model_bytes_window_moe.py`` against hand counts
and against what the program allocates, the configuration file against the
published config and its cut, the cell's listing, and the plain reference
beside it against the program's forward pass at a small size on the CPU.

Every entry of ``BENCHMARK.json`` is found BY NAME: no position in a list and
no count of cells, configurations or metrics is asserted, so that the next
added cell costs this file no test."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import manifest  # noqa: E402
from benchmark.lib import model_bytes_window_moe as mb  # noqa: E402

NAME = "laguna-xs2-int8"
CELL = "laguna-xs2.saturated-long"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


def by_name(entries, name):
    return next(e for e in entries if e["name"] == name)


PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "rms_norm_eps": 1e-06,
    "num_experts_per_tok": 8, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "gating": True, "sliding_window": 512, "partial_rotary_factor": 0.5,
    "moe_routed_scaling_factor": 2.5, "attention_bias": False,
}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_width_is_as_published(key):
    assert config()[key] == PUBLISHED[key]


def test_the_layer_pattern_heads_and_ropes_are_as_published():
    cfg = config()
    period = ["full_attention"] + ["sliding_attention"] * 3
    assert cfg["layer_types"] == period * 10
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    full = cfg["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["rope_theta"], full["factor"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"], full["partial_rotary_factor"]) == (
        "yarn", 500000, 64, 4096, 64, 1, 0.5)
    assert abs(full["attention_factor"] - 1.41589) < 1e-5
    assert cfg["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}


def test_the_cut_is_stated_key_by_key():
    cfg = config()
    assert cfg["reduced"] == ["num_experts"]
    assert (cfg["num_experts"], cfg["published_num_experts"],
            cfg["held_experts"]) == (32, 256, [0, 32])
    for word in ("8 chips of one v5e-8 host share each layer's routed "
                 "experts", "expert parallel, 32 of 256 a chip",
                 "data parallel", "no pipeline stage",
                 "every chip runs all 40 layers", "rank 0"):
        assert word in cfg["deployment"], word
    assumed = " ".join(cfg["assumed"])
    for word in ("ONE sigmoid gate a head", "per-head", "33.442B",
                 "NO selection bias", "renormalises over the chosen eight",
                 "no q/k norm", "attention_factor", "rotated dims only",
                 "the window counts the query itself", "bf16 rings",
                 "seeded weights", "unit RMS"):
        assert word in assumed, word
    srv = cfg["serving"]
    assert (srv["num_pages"], srv["max_decode_batch"], srv["page_size"],
            srv["max_prefill_len"], srv["max_context_tokens"]) == (
        7680, 48, 16, 512, 2560)
    assert srv["state_bytes_per_slot"] == mb.state_bytes_per_slot(cfg) == (
        62914560)
    profile = open(os.path.join(ROOT, cfg["profile"])).read()
    for size in ("num_layers: 40", "held_experts: [0, 32]",
                 "num_experts: 256", "window_num_heads: 64",
                 "num_heads: 48", "sliding_window: 512", "rotary_dim: 64",
                 "attn_gate: true", "max_pages_per_seq: 160",
                 "num_pages: 7680", "max_decode_batch: 48",
                 "enable_prefix_cache: false", "__SEED__"):
        assert size in profile, size


def test_catalog_keys_are_copied_whole():
    """Against the guide's catalog row, where the sandbox has it."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Laguna-XS.2")
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k
        else:
            assert cfg["published_" + k] == v, k


def test_the_profile_builds_the_catalog_model_at_the_cut():
    """``model_overrides`` restates the catalog entry at the cut, and the
    configuration file's Hugging Face keys give the same model."""
    import dataclasses

    import yaml

    from helix_tpu.models.common import LAGUNA_XS2, ModelConfig

    cfg = config()
    with open(os.path.join(ROOT, cfg["profile"])) as f:
        prof = yaml.safe_load(f.read().replace("__SEED__", "7"))
    over = dict(prof["models"][0]["model_overrides"])
    for key in ("layer_types", "held_experts"):
        over[key] = tuple(over[key])
    over["rope_scaling"] = tuple(sorted(over["rope_scaling"].items()))
    assert prof["models"][0]["name"] == cfg["model"] == LAGUNA_XS2.name
    served = dataclasses.replace(LAGUNA_XS2, **over)
    assert served == dataclasses.replace(LAGUNA_XS2, held_experts=(0, 32))
    assert ModelConfig.from_hf_config(cfg, name=cfg["model"]) == served
    eng = prof["models"][0]["engine"]
    assert set(eng) == {"max_decode_batch", "page_size", "max_prefill_len",
                        "kv_cache_dtype", "num_pages", "max_pages_per_seq",
                        "enable_prefix_cache"}
    assert eng["enable_prefix_cache"] is False


def test_parameter_count_against_the_issues_hand_count():
    """The issue's count from the published keys: 37.88M a sliding layer's
    attention, 29.46M a full layer's, 3.146M an expert, 33.442B whole (the
    row's "33.4B": ONE gate value a head), 5.96B at the cut."""
    cfg = config()
    p = mb.parameter_count(cfg)
    M = 1e6
    assert abs(p["window_attention"] / 30 / M - 37.88) < 0.01
    assert abs(p["full_attention"] / 10 / M - 29.46) < 0.01
    assert p["dense_mlp"] == 3 * 2048 * 8192
    assert p["held_experts"] == 39 * 32 * 3 * 2048 * 512
    assert p["shared_experts"] == 39 * 3 * 2048 * 512
    assert p["routers"] == 39 * 2048 * 256
    assert p["embedding"] == p["head"] == 100352 * 2048
    assert abs(p["total"] / 1e9 - 5.96) < 0.01
    whole = mb.parameter_count(dict(cfg, num_experts=256))
    assert abs(whole["total"] / 1e9 - 33.442) < 0.002


def test_bytes_are_what_the_program_allocates():
    """Weights, the rings and the page pool, byte for byte against
    ``init_params(int8=True)`` and ``CacheConfig`` (shapes only: nothing is
    allocated), and within 2% of the issue's 5.96 / 3.02 / 5.03 GB; a sliding
    layer holds no page and its bytes a slot do not grow with the
    sequence."""
    import jax

    from helix_tpu.engine.kv_cache import CacheConfig
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params

    cfg = config()
    model = ModelConfig.from_hf_config(cfg, name=cfg["model"])
    tree = jax.eval_shape(
        lambda: init_params(model, jax.random.PRNGKey(0), int8=True))
    allocated = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                    for a in jax.tree.leaves(tree))
    parts = mb.weight_bytes_by_part(cfg)
    assert parts["total"] == allocated == mb.weight_bytes(cfg)
    # four loop bodies: (full, dense), nine times ((sliding, experts) x 3,
    # (full, experts)), (sliding, experts) x 3
    assert sorted(k for k in tree if k.startswith("run")) == [
        "run00", "run01", "run02", "run19"]
    assert tree["run01"]["experts"]["w_gate"]["weight"].shape == (
        27, 32, 2048, 512)
    assert tree["run01"]["router"]["weight"].shape == (27, 2048, 256)
    assert tree["run01"]["wq"]["weight"].shape == (27, 2048, 64 * 128)
    assert tree["run02"]["wq"]["weight"].shape == (9, 2048, 48 * 128)
    assert tree["run01"]["attn_gate"]["weight"].shape == (27, 2048, 64)
    assert tree["run00"]["attn_gate"]["weight"].shape == (1, 2048, 48)
    srv = cfg["serving"]
    cc = CacheConfig(num_pages=srv["num_pages"], page_size=srv["page_size"],
                     max_pages_per_seq=160,
                     state_slots=srv["max_decode_batch"])
    assert cc.page_shapes(model) == ((10, 16, 8, 128), (10, 16, 8, 128))
    assert cc.page_bytes(model) == mb.page_bytes(cfg, 16) == 655360
    assert cc.state_shapes(model) == (
        ((30, 48, 512, 8, 128), "bfloat16"),) * 2
    rings, pages = mb.cache_bytes(cfg, 48, srv["num_pages"], 16)
    assert cc.state_bytes(model) == rings == 48 * 62914560
    assert srv["num_pages"] * cc.page_bytes(model) == pages
    assert mb.ring_bytes_per_slot_layer(cfg) == 2097152
    assert 160 * mb.page_bytes(cfg, 16) == 104857600
    for got, issue in ((allocated, 5.96e9), (rings, 3.02e9),
                       (pages, 5.03e9), (rings + pages, 8.05e9)):
        assert abs(got / issue - 1) < 0.02, (got, issue)
    # all layers' pages for the whole context would be 20.1 GB
    assert abs(48 * 2560 * 40 * mb.token_bytes(cfg) / 20.1e9 - 1) < 0.01


def test_a_decode_step_and_the_kernels_calls_by_hand():
    cfg = config()
    lengths = [1250] * 48
    ops, b = mb.window_decode_call(cfg, lengths)
    assert ops == 4 * 48 * 512 * 64 * 128
    assert b == 48 * 513 * 4096 + 2 * 48 * 64 * 128 * 2
    assert abs(b / 819e9 * 1e3 - 0.125) < 0.003           # ms a layer
    share, bound = mb.roofline_share(ops, b, 0.25e-3, PEAKS)
    assert bound == "hbm" and abs(share - 50.0) < 1.5
    ops, b = mb.full_decode_call(cfg, lengths)
    assert ops == 4 * 48 * 1251 * 48 * 128
    assert b == 48 * 1251 * 4096 + 2 * 48 * 48 * 128 * 2
    # a row under the window reads what it has written, no more
    ops_s, b_s = mb.window_decode_call(cfg, [100])
    assert ops_s == 4 * 101 * 64 * 128 and b_s == 101 * 4096 + 2 * 64 * 256
    ops, b = mb.window_chunk_call(cfg, 512, 512)
    assert ops == 4 * 512 * 512 * 64 * 128                 # 512 keys a query
    assert b == 1024 * 4096 + 2 * 512 * 64 * 128 * 2
    ops, _ = mb.window_chunk_call(cfg, 512, 0)
    assert ops == 4 * (512 * 513 // 2) * 64 * 128          # causal alone
    ops, b = mb.full_chunk_call(cfg, 512, 1024)
    assert ops == 4 * (512 * 1024 + 512 * 513 // 2) * 48 * 128
    assert b == 1536 * 4096 + 2 * 512 * 48 * 128 * 2
    step = mb.decode_step_bytes(cfg, 48, 48 * 1250, experts_touched=25)
    p = mb.weight_bytes_by_part(cfg)
    assert step == (p["total"] - p["embedding"] - p["held_experts"]
                    + p["held_experts"] * 25 / 32 + 48 * 2048
                    + 48 * 1250 * 10 * 4096 + 48 * 512 * 30 * 4096)
    # the issue's reckoning: 10.4 GB, 12.7 ms at 819 GB/s; K/V about half
    assert abs(step / 1e9 - 10.4) < 0.3
    kv = 48 * 1250 * 10 * 4096 + 48 * 512 * 30 * 4096
    assert 0.50 < kv / step < 0.56


def test_the_cell_is_listed_under_the_metrics_the_issue_names():
    bench = manifest.benchmark_json()
    entry = by_name(bench["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NAME, "saturated-long", 1)
    assert len(entry["why"]) <= 200
    cfg_entry = by_name(bench["configs"], NAME)
    assert cfg_entry["reduced"] == ["num_experts"]
    assert cfg_entry["file"] == "benchmark/configs/" + NAME + ".json"
    assert cfg_entry["source"] == config()["source"]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    for name in ("kernel.window_attn_share", "step.chunk_ms.window",
                 "kernel.attn_share.saturated", "kernel.grouped_mm_share",
                 "device.idle_share.saturated",
                 "loop.host_build_ms.saturated", "loop.admit_ms.saturated",
                 "loop.dispatch_ms.saturated", "loop.fetch_ms.saturated",
                 "loop.emit_ms.saturated", "sched.slot_occupancy",
                 "loop.exposed_host_ms.saturated"):
        assert name in listed, name
    # PR 37's nine host-account metrics are on the line of this cell's traced
    # runs too (PERF.md section 6), but ``test_benchmark_host_account.py``
    # pins their cells: a ``benchmark`` PR's to list
    for name in ("loop.claim_ms.saturated", "loop.plan_ms.saturated",
                 "loop.launch_ms.saturated", "loop.gc_ms.saturated",
                 "http.loop_cpu_ms.saturated"):
        assert name not in listed, name
    # not under another architecture's kernel, a dense-only metric or a
    # decode-only program's (a capture of this traffic may hold no t0)
    for name in ("kernel.moe_share", "kernel.mla_share",
                 "kernel.deltanet_share", "kernel.retention_share",
                 "step.decode_ms", "step.decode_hbm_share"):
        assert name not in listed, name
    assert not any(n.startswith("step.decode_ms") for n in listed)
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in ("kernel.window_attn_share",
                            "step.chunk_ms.window")}
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert {n: (m["moves"], m["layer"], m["source"], m["unit"])
            for n, m in new.items()} == {
        "kernel.window_attn_share": (
            "tpot_p95_ms.saturated", "kernels", "device_trace", "%"),
        "step.chunk_ms.window": (
            "tokens_per_s", "engine step", "device_trace", "ms")}
    assert {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", ())} == {
        "tokens_per_s", "tpot_p95_ms.saturated"}
    # every listed metric moves an end-to-end metric the cell reports
    moved = {m["moves"] for m in bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert moved <= {"tokens_per_s", "tpot_p95_ms.saturated"}


def test_every_new_name_resolves():
    c = manifest.cell(CELL)
    assert c["params"]["clients"] == 72
    assert c["cell_file"]["params"] == {"clients": 72}
    assert c["params"]["generator"] == "closed_loop"
    assert c["params"]["prompt_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 256,
        "max": 2048}
    assert c["params"]["max_tokens"] == {
        "dist": "uniform", "min": 256, "max": 384}
    assert (c["params"]["temperature"], c["params"]["pool_seed"]) == (1.0, 24)
    for key in ("users", "exercises", "bypasses"):
        assert c["cell_file"][key]
    assert "1.5 rows a held expert" in c["cell_file"]["exercises"]
    assert "more than its share" in c["cell_file"]["bypasses"]
    assert os.path.isfile(c["profile_template"])
    assert os.path.isfile(os.path.join(ROOT, c["config"]["reference"]))
    readers = {m["name"]: m["reader"] for m in c["per_layer"]}
    assert readers["kernel.window_attn_share"]["op"] == "^window_"
    assert "whole_op" not in readers["step.chunk_ms.window"]
    assert readers["step.chunk_ms.window"]["program"] == (
        "^jit_step_fn_t512_r1(_h)?\\(")
    from benchmark.lib.readers import READERS

    for name, spec in readers.items():
        assert spec["reduction"] in READERS, name
    srv = c["config"]["serving"]
    assert 2048 + 384 <= srv["max_context_tokens"] == 160 * srv["page_size"]
    assert 72 == srv["max_decode_batch"] * 3 // 2
    assert {m["name"] for m in c["end_to_end"]} == {
        "tokens_per_s", "tpot_p95_ms.saturated", "setup_s"}


def test_the_readers_read_a_trace_without_the_window_op_as_nothing():
    """On a synthetic summary: the chunk programs' mean and the kernel's
    share of busy time; a capture without a chunk program or the kernel (the
    parent's, or another model's) reads nothing and raises nothing."""
    from benchmark.lib.readers import READERS

    c = manifest.cell(CELL)
    readers = {m["name"]: m["reader"] for m in c["per_layer"]}
    dev = {"busy_s": 2.0, "modules": [
        {"name": "jit_step_fn_t0(1)", "dur_s": 0.09,
         "ops": {"window_attention_tpu": 30, "window_attention_tpu.1": 30}},
        {"name": "jit_step_fn_t512_r1(2)", "dur_s": 0.10,
         "ops": {"window_attention_tpu": 30}},
        {"name": "jit_step_fn_t512_r1_h(3)", "dur_s": 0.08,
         "ops": {"window_attention_tpu": 60}}],
        "ops": {"window_attention_tpu": [90, 0.3],
                "window_attention_tpu.1": [60, 0.1], "fusion.1": [5, 1.0]}}
    ctx = {"trace": {"devices": [dev], "window_s": 3.0},
           "config": c["config"]}
    spec = readers["step.chunk_ms.window"]
    assert READERS[spec["reduction"]](ctx, spec) == pytest.approx(90.0)
    spec = readers["kernel.window_attn_share"]
    assert READERS[spec["reduction"]](ctx, spec) == pytest.approx(20.0)
    bare = {"busy_s": 2.0, "ops": {"fusion.1": [5, 1.0]}, "modules": [
        {"name": "jit_step_fn_t0(1)", "dur_s": 0.09, "ops": {"fusion": 3}}]}
    ctx = {"trace": {"devices": [bare], "window_s": 3.0},
           "config": c["config"]}
    for name in ("step.chunk_ms.window", "kernel.window_attn_share"):
        spec = readers[name]
        assert READERS[spec["reduction"]](ctx, spec) is None


def test_the_reference_beside_the_configuration_loads_and_runs():
    """``<name>.reference.py`` is loaded by path; at a small size its forward
    is the program's (float32, the CPU): 1e-5 of logits of spread 0.16."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import forward, init_params, prefill_attn_fn

    cfg = config()
    spec = importlib.util.spec_from_file_location(
        "laguna_reference", os.path.join(ROOT, cfg["reference"]))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.CONFIG == cfg
    L = 8
    rope = {k: dict(v) for k, v in cfg["rope_parameters"].items()
            if isinstance(v, dict)}
    rope["full_attention"].update(
        original_max_position_embeddings=16, beta_fast=4)
    small = dict(
        cfg, vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_attention_heads=6, num_key_value_heads=2, head_dim=16,
        num_experts_per_tok=4, num_experts=4, published_num_experts=16,
        held_experts=[0, 4], sliding_window=8, num_hidden_layers=L,
        layer_types=cfg["layer_types"][:L],
        mlp_layer_types=cfg["mlp_layer_types"][:L],
        num_attention_heads_per_layer=[6, 8, 8, 8] * (L // 4),
        rope_parameters=rope)
    model = dataclasses.replace(
        ModelConfig.from_hf_config(small, name="small"), dtype="float32")
    params = init_params(model, jax.random.PRNGKey(5))
    toks = jnp.asarray(np.random.default_rng(5).integers(1, 256, size=70))
    got, _ = forward(params, model, toks[None], jnp.arange(70)[None],
                     attn_fn=prefill_attn_fn)
    want = np.asarray(mod.forward(params, small, toks))
    assert np.abs(np.asarray(got[0]) - want).max() < 1e-5
    assert want.std() > 0.05
