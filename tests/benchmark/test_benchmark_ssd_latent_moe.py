"""The new configuration's yardstick (ISSUE 45): the operations-and-bytes
functions of ``benchmark/lib/model_bytes_ssd_latent_moe.py`` against hand
counts and against what the program allocates, the configuration file against
the published config and its cut, the cell's listing, and the plain reference
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
from benchmark.lib import model_bytes_ssd_latent_moe as mb  # noqa: E402

NAME = "nemotron-3-super-120b-a12b-int8"
CELL = "nemotron-3-super-120b-a12b.saturated-long"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "num_nextn_predict_layers"]


def config():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


def by_name(entries, name):
    return next(e for e in entries if e["name"] == name)


PUBLISHED = {
    "model_type": "nemotron_h", "vocab_size": 131072, "hidden_size": 4096,
    "intermediate_size": 2688, "num_attention_heads": 32,
    "num_key_value_heads": 2, "head_dim": 128, "mamba_num_heads": 128,
    "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
    "conv_kernel": 4, "expand": 2, "chunk_size": 128,
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376, "n_shared_experts": 1,
    "num_experts_per_tok": 22, "routed_scaling_factor": 5,
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "norm_eps": 1e-05, "use_conv_bias": True, "mamba_proj_bias": False,
    "tie_word_embeddings": False, "residual_in_fp32": False,
    "max_position_embeddings": 262144,
}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_width_is_as_published(key):
    assert config()[key] == PUBLISHED[key]


def test_the_cut_is_stated_key_by_key():
    cfg = config()
    assert cfg["reduced"] == REDUCED
    assert cfg["published_hybrid_override_pattern"] == PUBLISHED_PATTERN
    assert len(PUBLISHED_PATTERN) == cfg["published_num_hidden_layers"] == 88
    lo, hi = cfg["kept_published_layers"]
    assert (lo, hi) == (25, 46)
    kept = PUBLISHED_PATTERN[lo:hi + 1]
    assert cfg["hybrid_override_pattern"] == kept == "*EMEMEMEMEM*EMEMEMEMEM"
    assert cfg["num_hidden_layers"] == len(kept) == 22
    # two whole periods of the published 5 : 5 : 1, cut where a period starts
    assert (kept.count("M"), kept.count("E"), kept.count("*")) == (10, 10, 2)
    starts = [i for i, ch in enumerate(PUBLISHED_PATTERN) if ch == "*"]
    assert lo in starts and hi + 1 in starts
    assert (cfg["n_routed_experts"], cfg["published_n_routed_experts"],
            cfg["held_experts"]) == (128, 512, [0, 128])
    assert (cfg["num_nextn_predict_layers"],
            cfg["published_num_nextn_predict_layers"]) == (0, 1)
    # no width, head count, state size, top-k or vocabulary row is reduced
    for key in cfg["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")), key
    text = " ".join(cfg["assumed"])
    for words in ("no rotary embedding", "BEFORE the norm", "z | x | B | C",
                  "16 heads of a group", "1e-6", "W_fc1", "NO gate matrix",
                  "multi-token prediction", "float32 h", "bfloat16 conv tail",
                  "A_log", "D uniform", "selection bias"):
        assert words in text, words
    assert "four pipeline stages" in cfg["deployment"]
    assert "128 of 512 a chip" in cfg["deployment"]
    assert "25 to 46" in cfg["deployment"]


def test_catalog_keys_are_copied_whole():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == ROW)
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cfg["published_" + key] == value, key
        else:
            assert cfg[key] == value, key


def test_the_profile_builds_the_catalog_model_at_the_cut():
    """``model_overrides`` restates the catalog entry at the cut, and the
    configuration file's Hugging Face keys give the same model."""
    import dataclasses

    import yaml

    from helix_tpu.models.common import NEMOTRON3_SUPER_120B, ModelConfig

    cfg = config()
    with open(os.path.join(ROOT, cfg["profile"])) as f:
        prof = yaml.safe_load(f.read().replace("__SEED__", "7"))
    over = dict(prof["models"][0]["model_overrides"])
    over["held_experts"] = tuple(over["held_experts"])
    assert prof["models"][0]["name"] == cfg["model"] == (
        NEMOTRON3_SUPER_120B.name)
    served = dataclasses.replace(NEMOTRON3_SUPER_120B, **over)
    assert served == dataclasses.replace(
        NEMOTRON3_SUPER_120B, num_layers=22, held_experts=(0, 128),
        hybrid_pattern="*EMEMEMEMEM*EMEMEMEMEM")
    assert ModelConfig.from_hf_config(cfg, name=cfg["model"]) == served
    # 22 one-branch layers run as 12 blocks in three loop bodies
    assert len(served.mixers) == 12 and served.loop_bodies == 3
    assert served.ffns.count("none") == 2
    eng = prof["models"][0]["engine"]
    assert set(eng) == {"max_decode_batch", "page_size", "max_prefill_len",
                        "kv_cache_dtype", "num_pages", "max_pages_per_seq",
                        "enable_prefix_cache"}
    assert eng["enable_prefix_cache"] is False
    srv = cfg["serving"]
    assert (eng["max_decode_batch"], eng["num_pages"], eng["page_size"],
            eng["max_prefill_len"]) == (
        srv["max_decode_batch"], srv["num_pages"], srv["page_size"],
        srv["max_prefill_len"]) == (64, 10240, 16, 512)


def test_parameter_count_against_the_issues_hand_count():
    """The issue's count from the published keys: 109.64 M a Mamba-2 mixer,
    35.66 M an attention layer, 54.53 M an expert layer beside its experts of
    5.505 M each, 2 x 536.9 M in embedding and head: 120.67 B whole, 12.77 B
    active at top-22, 77.9 M a layer beside the experts; 9.84 GB kept."""
    cfg = config()
    p = mb.parameter_count(cfg)
    M = 1e6
    assert abs(p["mamba_mixers"] / 10 / M - 109.64) < 0.01
    assert abs(p["attention"] / 2 / M - 35.66) < 0.01
    assert abs(p["expert_layers_beside_the_routed"] / 10 / M - 54.53) < 0.01
    assert p["one_expert"] == 2 * 1024 * 2688 == 5505024
    assert p["held_experts"] == 10 * 128 * 5505024
    assert p["embedding"] == p["head"] == 131072 * 4096
    assert abs(p["total"] / 1e9 - 9.84) < 0.01
    whole, active = mb.published_parameter_count(cfg)
    assert abs(whole["total"] / 1e9 - 120.67) < 0.005
    assert abs(active / 1e9 - 12.77) < 0.005
    beside = whole["total"] - whole["held_experts"] - 2 * whole["embedding"]
    assert abs(beside / 88 / M - 77.9) < 0.1


def test_bytes_are_what_the_program_allocates():
    """Weights, the state pool and the page pool, byte for byte against
    ``init_params(int8=True)`` and ``CacheConfig`` (shapes only: nothing is
    allocated), and within 2% of the issue's 9.84 / 2.72 / 0.34 GB; no gate
    matrix, no feed-forward weight in a block the pattern gives none."""
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
    # three loop bodies, twice: (attention, experts), (Mamba-2, experts) x 4,
    # (Mamba-2 alone)
    assert sorted(k for k in tree if k.startswith("run")) == [
        "run00", "run01", "run02"]
    assert tree["run01"]["experts"]["w_up"]["weight"].shape == (
        8, 128, 1024, 2688)
    assert tree["run01"]["experts"]["w_down"]["weight"].shape == (
        8, 128, 2688, 1024)
    assert tree["run00"]["router"]["weight"].shape == (2, 4096, 512)
    assert tree["run00"]["fc1"]["weight"].shape == (2, 4096, 1024)
    assert tree["run00"]["fc2"]["weight"].shape == (2, 1024, 4096)
    assert tree["run00"]["shared"]["w_up"]["weight"].shape == (2, 4096, 5376)
    assert tree["run00"]["wk"]["weight"].shape == (2, 4096, 2 * 128)
    assert tree["run01"]["in_xbc"]["weight"].shape == (8, 4096, 10240)
    assert tree["run02"]["in_z"]["weight"].shape == (2, 4096, 8192)
    assert tree["run02"]["in_dt"]["weight"].shape == (2, 4096, 128)
    assert not {"mlp_norm", "router", "experts", "shared", "fc1", "w_up"} & (
        set(tree["run02"]))
    names = {str(getattr(k, "key", k)) for path, _ in
             jax.tree_util.tree_flatten_with_path(tree)[0] for k in path}
    assert "w_gate" not in names
    srv = cfg["serving"]
    cc = CacheConfig(num_pages=srv["num_pages"], page_size=srv["page_size"],
                     max_pages_per_seq=160,
                     state_slots=srv["max_decode_batch"])
    assert cc.page_shapes(model) == ((2, 16, 2, 128), (2, 16, 2, 128))
    assert cc.page_bytes(model) == mb.page_bytes(cfg, 16) == 32768
    # h [128 heads, 64, 128] float32, two heads to a lane tile
    assert cc.state_shapes(model) == (
        ((10, 64, 3, 10240), "bfloat16"), ((10, 64, 64, 128, 128), "float32"))
    assert mb.state_bytes_per_slot_layer(cfg) == 4194304 + 61440
    assert mb.state_bytes_per_slot(cfg) == srv["state_bytes_per_slot"]
    state = cc.state_bytes(model)
    assert state == 64 * mb.state_bytes_per_slot(cfg)
    pages = srv["num_pages"] * cc.page_bytes(model)
    for got, issue in ((allocated, 9.84e9), (state, 2.72e9), (pages, 0.34e9),
                       (allocated + state + pages, 12.9e9)):
        assert abs(got / issue - 1) < 0.02, (got, issue)


def test_a_decode_step_and_the_kernels_calls_by_hand():
    cfg = config()
    # 64 rows x 22 choices over 512 experts: 2.75 rows a held expert, about
    # 94% of the 128 touched
    touched = mb.experts_touched(cfg, 64)
    assert abs(touched / 128 - 0.94) < 0.005
    step = mb.decode_step_bytes(cfg, 64, 80000)
    p = mb.weight_bytes_by_part(cfg)
    by_hand = (p["total"] - p["embedding"] - p["held_experts"]
               + 10 * touched * p["one_expert"] + 64 * 4096
               + 2 * 64 * 42557440 + 80000 * 2048)
    assert step == by_hand
    # the issue's reckoning: 14.4 GB, a floor of 17.6 ms at 819 GB/s; the
    # state 37% of it and the held experts 46%
    assert abs(step / 14.4e9 - 1) < 0.02
    assert abs(step / 819e9 * 1e3 / 17.6 - 1) < 0.02
    assert abs(2 * 64 * 42557440 / step - 0.37) < 0.01
    assert abs(10 * touched * p["one_expert"] / step - 0.46) < 0.01
    ops, bytes_ = mb.ssd_decode_call(cfg, 64)
    entries = 64 * 128 * 64 * 128
    assert ops == 5 * entries
    assert bytes_ == 2 * entries * 4 + 64 * 64 * 5 * 128 * 4
    share, bound = mb.roofline_share(
        ops, bytes_, 1e-3, {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "hbm" and abs(share - bytes_ / 819e9 / 1e-3 * 100) < 1e-6
    ops, bytes_ = mb.ssd_chunk_call(cfg, 512)
    assert ops == 2 * 4 * (8 * 128 * 128 * 128 + 128 * (
        128 * 128 * 64 + 2 * 128 * 64 * 128))
    assert bytes_ == 2 * 4194304 + 512 * (2 * 8192 + 2048 + 256) * 4
    ops, bytes_ = mb.grouped_product_call(cfg, 352)
    assert ops == 2 * 352 * 2 * 1024 * 2688
    assert bytes_ == 128 * p["one_expert"] + 352 * (
        1024 * 2 + 2 * 2688 * 2 + 1024 * 4)


def test_the_cell_is_listed_where_the_issue_says():
    bench = manifest.benchmark_json()
    entry = by_name(bench["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NAME, "saturated-long", 1)
    assert len(entry["why"]) <= 200
    cfg_entry = by_name(bench["configs"], NAME)
    assert cfg_entry["reduced"] == REDUCED
    assert cfg_entry["file"] == "benchmark/configs/" + NAME + ".json"
    assert cfg_entry["source"] == config()["source"]
    assert len(cfg_entry["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {
        "kernel.ssd_share", "step.chunk_ms.ssd", "kernel.grouped_mm_share",
        "kernel.attn_share.saturated", "device.idle_share.saturated",
        "sched.slot_occupancy", "loop.host_build_ms.saturated",
        "loop.admit_ms.saturated", "loop.prefill_sync_ms.saturated",
        "loop.dispatch_ms.saturated", "loop.fetch_ms.saturated",
        "loop.reconcile_ms.saturated", "loop.emit_ms.saturated",
        "loop.deliver_ms.saturated", "loop.emit_queue_wait_ms.saturated",
        "loop.exposed_host_ms.saturated"}
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in ("kernel.ssd_share", "step.chunk_ms.ssd")}
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert {n: (m["moves"], m["layer"], m["source"], m["unit"], m["better"])
            for n, m in new.items()} == {
        "kernel.ssd_share": (
            "tpot_p95_ms.saturated", "kernels", "device_trace", "%", "lower"),
        "step.chunk_ms.ssd": (
            "tokens_per_s", "engine step", "device_trace", "ms", "lower")}
    assert {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", ())} == {
        "tokens_per_s", "tpot_p95_ms.saturated"}
    moved = {m["moves"] for m in bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert moved <= {"tokens_per_s", "tpot_p95_ms.saturated"}


def test_every_new_name_resolves():
    c = manifest.cell(CELL)
    assert c["params"]["clients"] == 96
    assert c["cell_file"]["params"] == {}
    assert c["params"]["generator"] == "closed_loop"
    assert c["params"]["prompt_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 256,
        "max": 2048}
    assert c["params"]["max_tokens"] == {
        "dist": "uniform", "min": 256, "max": 384}
    assert (c["params"]["temperature"], c["params"]["pool_seed"]) == (1.0, 24)
    for key in ("users", "exercises", "bypasses"):
        assert c["cell_file"][key]
    assert "2.75 rows a held expert" in c["cell_file"]["exercises"]
    assert "would give it 11" in c["cell_file"]["bypasses"]
    assert "their own share" in c["cell_file"]["bypasses"]
    assert os.path.isfile(c["profile_template"])
    assert os.path.isfile(os.path.join(ROOT, c["config"]["reference"]))
    readers = {m["name"]: m["reader"] for m in c["per_layer"]}
    assert readers["kernel.ssd_share"]["op"] == "^ssd_"
    assert "whole_op" not in readers["step.chunk_ms.ssd"]
    assert readers["step.chunk_ms.ssd"]["program"] == (
        "^jit_step_fn_t512_r1(_h)?\\(")
    from benchmark.lib.readers import READERS

    for name, spec in readers.items():
        assert spec["reduction"] in READERS, name
    srv = c["config"]["serving"]
    assert 2048 + 384 <= srv["max_context_tokens"] == 160 * srv["page_size"]
    assert 96 == srv["max_decode_batch"] * 3 // 2
    assert {m["name"] for m in c["end_to_end"]} == {
        "tokens_per_s", "tpot_p95_ms.saturated", "setup_s"}


def test_the_readers_read_a_trace_without_an_ssd_op_as_nothing():
    """On a synthetic summary: the chunk programs' mean and the kernel's
    share of busy time; a capture without a chunk program or the kernel (the
    parent's, or another model's) reads nothing and raises nothing."""
    from benchmark.lib.readers import READERS

    c = manifest.cell(CELL)
    readers = {m["name"]: m["reader"] for m in c["per_layer"]}
    dev = {"busy_s": 2.0, "modules": [
        {"name": "jit_step_fn_t0(1)", "dur_s": 0.09,
         "ops": {"ssd_decode_tpu": 30, "ssd_decode_tpu.1": 30}},
        {"name": "jit_step_fn_t512_r1(2)", "dur_s": 0.10,
         "ops": {"ssd_decode_tpu": 30}},
        {"name": "jit_step_fn_t512_r1_h(3)", "dur_s": 0.08,
         "ops": {"ssd_decode_tpu": 60}}],
        "ops": {"ssd_decode_tpu": [90, 0.3], "ssd_decode_tpu.1": [60, 0.1],
                "fusion.1": [5, 1.0]}}
    ctx = {"trace": {"devices": [dev], "window_s": 3.0},
           "config": c["config"]}
    spec = readers["step.chunk_ms.ssd"]
    assert READERS[spec["reduction"]](ctx, spec) == pytest.approx(90.0)
    spec = readers["kernel.ssd_share"]
    assert READERS[spec["reduction"]](ctx, spec) == pytest.approx(20.0)
    bare = {"busy_s": 2.0, "ops": {"fusion.1": [5, 1.0]}, "modules": [
        {"name": "jit_step_fn_t0(1)", "dur_s": 0.09, "ops": {"fusion": 3}}]}
    ctx = {"trace": {"devices": [bare], "window_s": 3.0},
           "config": c["config"]}
    for name in ("step.chunk_ms.ssd", "kernel.ssd_share"):
        spec = readers[name]
        assert READERS[spec["reduction"]](ctx, spec) is None


def test_the_reference_beside_the_configuration_loads_and_runs():
    """``<name>.reference.py`` is loaded by path; at a small size its forward
    is the program's (float32, the CPU): 1e-5 of logits of spread 0.2.  It
    imports nothing from the program."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import forward, init_params, prefill_attn_fn

    cfg = config()
    spec = importlib.util.spec_from_file_location(
        "nemotron_reference", os.path.join(ROOT, cfg["reference"]))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.CONFIG == cfg
    with open(os.path.join(
            ROOT, "benchmark", "lib",
            "reference_ssd_latent_moe_decoder.py")) as f:
        assert "helix_tpu" not in f.read().split('"""', 2)[2]
    small = dict(
        cfg, vocab_size=256, hidden_size=64, intermediate_size=48,
        moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
        moe_latent_size=32, num_attention_heads=4, head_dim=16,
        mamba_num_heads=8, mamba_head_dim=32, n_groups=2, ssm_state_size=16,
        chunk_size=8, expand=4, num_experts_per_tok=6, n_routed_experts=4,
        published_n_routed_experts=16, held_experts=[0, 4],
        num_hidden_layers=11,
        hybrid_override_pattern=cfg["hybrid_override_pattern"][:11])
    model = dataclasses.replace(
        ModelConfig.from_hf_config(small, name="small"), dtype="float32")
    params = init_params(model, jax.random.PRNGKey(5))
    toks = jnp.asarray(np.random.default_rng(5).integers(1, 256, size=37))
    got, _ = forward(params, model, toks[None], jnp.arange(37)[None],
                     attn_fn=prefill_attn_fn)
    want = np.asarray(mod.forward(params, small, toks))
    assert np.abs(np.asarray(got[0]) - want).max() < 1e-5
    assert want.std() > 0.05
