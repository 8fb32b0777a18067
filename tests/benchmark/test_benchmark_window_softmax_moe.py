"""The new configuration's yardstick (ISSUE 48): the operations-and-bytes
functions of ``benchmark/lib/model_bytes_window_softmax_moe.py`` against hand
counts and against what the program allocates, the configuration file against
the published config (nothing cut), the cell's own entries, and the plain
reference beside it against the program's forward pass at a small size on the
CPU.

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
from benchmark.lib import model_bytes_window_softmax_moe as mb  # noqa: E402

NAME = "mellum2-12b-a2.5b-int8"
CELL = "mellum2-12b-a2.5b.saturated-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW = {"kernel.window_attn_share.8k": (
           "tpot_p95_ms.saturated", "kernels", "device_trace", "%"),
       "step.chunk_ms.window.8k": (
           "tokens_per_s", "engine step", "device_trace", "ms"),
       "step.decode_ms.window": (
           "tpot_p95_ms.saturated", "engine step", "device_trace", "ms")}


def config():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


def by_name(entries, name):
    return next(e for e in entries if e["name"] == name)


PUBLISHED = {
    "model_type": "mellum", "vocab_size": 98304, "hidden_size": 2304,
    "intermediate_size": 7168, "num_hidden_layers": 28,
    "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "rms_norm_eps": 1e-06, "num_experts": 64, "num_experts_per_tok": 8,
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "tie_word_embeddings": False, "sliding_window": 1024,
    "use_sliding_window": True, "attention_bias": False,
    "hidden_act": "silu",
}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_width_is_as_published(key):
    assert config()[key] == PUBLISHED[key]


def test_the_layer_pattern_and_ropes_are_as_published():
    cfg = config()
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["layer_types"] == period * 7
    assert cfg["mlp_layer_types"] == ["sparse"] * 28
    full = cfg["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["rope_theta"], full["factor"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"], full["attention_factor"]) == (
        "yarn", 500000, 16, 8192, 32, 1, 1.2772588722239782)
    assert cfg["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 500000}


def test_nothing_is_cut_and_the_file_says_what_it_assumes():
    cfg = config()
    assert cfg["reduced"] == []
    assert "held_experts" not in cfg and "published_num_experts" not in cfg
    assert cfg["deployment"] == (
        "int8 weight-only, bf16 pages and rings; one v5e chip holds the "
        "whole model: 28 layers, 64 experts a layer, 98,304 rows; no layer "
        "is shared with another chip and no stage follows")
    assumed = " ".join(cfg["assumed"])
    for word in ("pre-norm residual block", "no q/k norm",
                 "max_window_layers", "qk_norm: true is one field",
                 "pairs (i, i + 64)", "attention_factor",
                 "128 ** -0.5 and nothing else",
                 "the window counts the query itself",
                 "softmax(x W_r) over all 64", "no shared expert",
                 "bf16 rings", "seeded weights", "unit RMS"):
        assert word in assumed, word
    notes = " ".join(cfg["notes"])
    for word in ("NOTHING is cut", "multi-token-prediction (MTP) head",
                 "12,149,915,904", "12,181,032,448", "44,040,192",
                 "229,376", "528,482,304", "1,497,595,904",
                 "T(4,128)(2,1)", "14.86 GB", "refused by name"):
        assert word in notes, word
    srv = cfg["serving"]
    assert (srv["num_pages"], srv["max_decode_batch"], srv["page_size"],
            srv["max_prefill_len"], srv["max_pages_per_seq"],
            srv["max_context_tokens"], srv["enable_prefix_cache"],
            srv["weight_dtype"], srv["kv_dtype"]) == (
        6529, 12, 16, 512, 544, 8704, False, "int8", "bfloat16")
    assert srv["num_pages"] == 12 * 544 + 1
    assert srv["max_context_tokens"] == 544 * 16
    assert srv["state_bytes_per_slot"] == mb.state_bytes_per_slot(cfg) == (
        44040192)
    profile = open(os.path.join(ROOT, cfg["profile"])).read()
    for size in ("num_layers: 28", "num_experts: 64", "num_heads: 32",
                 "num_kv_heads: 4", "sliding_window: 1024",
                 "vocab_size: 98304", "moe_scoring: softmax",
                 "moe_renormalize: true", "max_pages_per_seq: 544",
                 "num_pages: 6529", "max_decode_batch: 12",
                 "enable_prefix_cache: false", "__SEED__"):
        assert size in profile, size
    assert "held_experts" not in profile


def test_catalog_keys_are_copied_whole():
    """Against the guide's catalog row, where the sandbox has it."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        assert cfg[k] == v, k


def test_the_profile_builds_the_catalog_model():
    """``model_overrides`` restates the catalog entry, and the configuration
    file's Hugging Face keys give the same model."""
    import dataclasses

    import yaml

    from helix_tpu.models.common import MELLUM2_12B, ModelConfig

    cfg = config()
    with open(os.path.join(ROOT, cfg["profile"])) as f:
        prof = yaml.safe_load(f.read().replace("__SEED__", "7"))
    over = dict(prof["models"][0]["model_overrides"])
    over["layer_types"] = tuple(over["layer_types"])
    over["rope_scaling"] = tuple(sorted(over["rope_scaling"].items()))
    assert prof["models"][0]["name"] == cfg["model"] == MELLUM2_12B.name
    assert dataclasses.replace(MELLUM2_12B, **over) == MELLUM2_12B
    assert ModelConfig.from_hf_config(cfg, name=cfg["model"]) == MELLUM2_12B
    eng = prof["models"][0]["engine"]
    assert set(eng) == {"max_decode_batch", "page_size", "max_prefill_len",
                        "kv_cache_dtype", "num_pages", "max_pages_per_seq",
                        "enable_prefix_cache"}
    srv = cfg["serving"]
    assert (eng["max_decode_batch"], eng["page_size"], eng["max_prefill_len"],
            eng["num_pages"], eng["max_pages_per_seq"],
            eng["enable_prefix_cache"]) == (
        srv["max_decode_batch"], srv["page_size"], srv["max_prefill_len"],
        srv["num_pages"], srv["max_pages_per_seq"], False)


def test_parameter_count_against_the_issues_hand_count():
    """ISSUE 48's count from the published keys: attention a layer
    21,233,664; an expert 6,193,152, 64 of them 396,361,728, the router
    147,456; a layer 417,742,848; embedding and head 0.453 B: 12.15 B, of
    which a token multiplies 2.44 B."""
    cfg = config()
    p = mb.parameter_count(cfg)
    assert p["window_attention"] == 21 * 21233664
    assert p["full_attention"] == 7 * 21233664
    assert 21233664 == 2304 * 4096 * 2 + 2304 * 512 * 2
    assert p["held_experts"] == 28 * 64 * 6193152 == 28 * 396361728
    assert 6193152 == 3 * 2304 * 896
    assert p["routers"] == 28 * 147456
    assert p["dense_mlp"] == p["shared_experts"] == 0
    assert p["embedding"] == p["head"] == 98304 * 2304
    per_layer = 21233664 + 396361728 + 147456
    assert per_layer == 417742848
    assert p["total"] == 28 * per_layer + 2 * 98304 * 2304 + p["norms"] == (
        12149915904)
    assert abs(p["total"] / 1e9 - 12.15) < 0.005
    assert abs(28 * per_layer / 1e9 - 11.70) < 0.005
    assert abs(mb.active_parameter_count(cfg) / 1e9 - 2.44) < 0.01


def test_bytes_are_what_the_program_allocates():
    """Weights, the rings and the page pool, byte for byte against
    ``init_params(int8=True)`` and ``CacheConfig`` (shapes only: nothing is
    allocated), and the issue's 12.15 / 0.53 / 1.50 / 14.2 GB; a sliding
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
    assert parts["total"] == allocated == mb.weight_bytes(cfg) == 12181032448
    # two loop bodies, seven times: (sliding, experts) x 3, (full, experts)
    assert sorted(k for k in tree if k.startswith("run")) == [
        "run00", "run01"]
    assert tree["run00"]["experts"]["w_gate"]["weight"].shape == (
        21, 64, 2304, 896)
    assert tree["run01"]["experts"]["w_down"]["weight"].shape == (
        7, 64, 896, 2304)
    assert tree["run00"]["router"]["weight"].shape == (21, 2304, 64)
    assert tree["run00"]["wq"]["weight"].shape == (21, 2304, 32 * 128)
    assert tree["run01"]["wk"]["weight"].shape == (7, 2304, 4 * 128)
    assert tree["lm_head"]["weight"].shape == (2304, 98304)
    for absent in ("attn_gate", "shared", "expert_bias", "q_norm"):
        assert absent not in tree["run00"] and absent not in tree["run01"]
    srv = cfg["serving"]
    cc = CacheConfig(num_pages=srv["num_pages"], page_size=srv["page_size"],
                     max_pages_per_seq=srv["max_pages_per_seq"],
                     state_slots=srv["max_decode_batch"])
    assert cc.page_shapes(model) == ((7, 16, 4, 128), (7, 16, 4, 128))
    assert cc.page_bytes(model) == mb.page_bytes(cfg, 16) == 229376
    assert mb.token_bytes(cfg) * 7 == 14336
    assert cc.state_shapes(model) == (
        ((21, 12, 1024, 4, 128), "bfloat16"),) * 2
    rings, pages = mb.cache_bytes(cfg, 12, srv["num_pages"], 16)
    assert cc.state_bytes(model) == rings == 12 * 44040192 == 528482304
    assert srv["num_pages"] * cc.page_bytes(model) == pages == 1497595904
    assert mb.ring_bytes_per_slot_layer(cfg) == 2097152       # Laguna's
    assert cc.max_seq_len == srv["max_context_tokens"] == 8704
    for got, issue in ((allocated, 12.15e9), (rings, 0.53e9),
                       (pages, 1.50e9), (allocated + rings + pages, 14.2e9)):
        assert abs(got / issue - 1) < 0.01, (got, issue)
    # all layers' pages for the whole context would be 6.0 GB, not 1.5
    assert abs(12 * 8704 * 28 * mb.token_bytes(cfg) / 5.99e9 - 1) < 0.01


def test_a_decode_step_and_the_kernels_calls_by_hand():
    cfg = config()
    lengths = [4300] * 12
    ops, b = mb.window_decode_call(cfg, lengths)
    assert ops == 4 * 12 * 1024 * 32 * 128
    assert b == 12 * 1025 * 2048 + 2 * 12 * 32 * 128 * 2
    ops, b = mb.full_decode_call(cfg, lengths)
    assert ops == 4 * 12 * 4301 * 32 * 128
    assert b == 12 * 4301 * 2048 + 2 * 12 * 32 * 128 * 2
    # a chunk far past the window: every query sees 1,024 keys
    ops, b = mb.window_chunk_call(cfg, 512, 4096)
    assert ops == 4 * 512 * 1024 * 32 * 128
    assert b == 1536 * 2048 + 2 * 512 * 32 * 128 * 2
    ops, b = mb.full_chunk_call(cfg, 512, 8192)
    assert ops == 4 * (512 * 8192 + 512 * 513 // 2) * 32 * 128
    assert b == 8704 * 2048 + 2 * 512 * 32 * 128 * 2
    # ... and what the paged kernel WALKS for that chunk: the history once
    # a query block of 8, 64 times its 117 MB over the seven layers
    assert mb.pages_walked([8192], 16, 512, 8) == 512 * 64
    walked = mb.chunk_page_bytes_walked(cfg, 512, 8192, 16)
    assert walked == 512 * 64 * 229376 and abs(walked / 7.52e9 - 1) < 0.01
    assert mb.pages_walked(lengths, 16) == 12 * 269
    # 12 rows x 8 / 64 = 1.5 rows an expert a decode step, 64 a chunk
    assert mb.expert_rows(cfg, 12) == 1.5 and mb.expert_rows(cfg, 512) == 64
    touched = mb.experts_touched(cfg, 12)
    assert touched == 64 * (1 - (7 / 8) ** 12) and 51 < touched < 51.2
    assert mb.experts_touched(cfg, 524) > 63.99
    step = mb.decode_step_bytes_expected(cfg, lengths)
    p = mb.weight_bytes_by_part(cfg)
    assert step == (p["total"] - p["embedding"] - p["held_experts"]
                    + p["held_experts"] * touched / 64 + 12 * 2304
                    + 12 * 4300 * 7 * 2048 + 12 * 1024 * 21 * 2048)
    # the prediction's reckoning: 10.97 GB, 13.4 ms at 819 GB/s; the experts
    # four fifths of it, rings and pages a ninth
    assert abs(step / 1e9 - 10.97) < 0.05
    assert abs(step / 819e9 * 1e3 - 13.4) < 0.1
    assert 0.80 < p["held_experts"] * touched / 64 / step < 0.82
    kv = 12 * 4300 * 7 * 2048 + 12 * 1024 * 21 * 2048
    assert 0.11 < kv / step < 0.12
    share, bound = mb.roofline_share(0, step, 18e-3, PEAKS)
    assert bound == "hbm" and 73 < share < 76


def test_the_cells_own_entries():
    bench = manifest.benchmark_json()
    entry = by_name(bench["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        NAME, "saturated-long", 1)
    assert len(entry["why"]) <= 200
    cfg_entry = by_name(bench["configs"], NAME)
    assert cfg_entry["reduced"] == []
    assert cfg_entry["file"] == "benchmark/configs/" + NAME + ".json"
    assert cfg_entry["source"] == config()["source"]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    for name in (*NEW, "kernel.attn_share.saturated",
                 "kernel.grouped_mm_share", "device.idle_share.saturated",
                 "sched.slot_occupancy", "loop.exposed_host_ms.saturated",
                 "loop.host_build_ms.saturated", "loop.admit_ms.saturated",
                 "loop.prefill_sync_ms.saturated",
                 "loop.dispatch_ms.saturated", "loop.fetch_ms.saturated",
                 "loop.reconcile_ms.saturated", "loop.emit_ms.saturated",
                 "loop.deliver_ms.saturated",
                 "loop.emit_queue_wait_ms.saturated"):
        assert name in listed, name
    # the eighteen host-account metrics pin their cells elsewhere
    # (``test_benchmark_host_account.py``); Laguna's two pin its cell alone
    # (``test_benchmark_window_moe.py``), which is why they have twins here
    for name in ("loop.claim_ms.saturated", "loop.plan_ms.saturated",
                 "loop.launch_ms.saturated", "loop.gc_ms.saturated",
                 "http.loop_cpu_ms.saturated", "kernel.window_attn_share",
                 "step.chunk_ms.window", "kernel.moe_share",
                 "kernel.mla_share", "kernel.deltanet_share",
                 "kernel.retention_share", "kernel.ssd_share",
                 "step.decode_ms", "step.decode_hbm_share",
                 "step.decode_ms.retention"):
        assert name not in listed, name
    new = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert set(new) == set(NEW)
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert {n: (m["moves"], m["layer"], m["source"], m["unit"])
            for n, m in new.items()} == NEW
    assert all(m["better"] == "lower" for m in new.values())
    assert {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", ())} == {
        "tokens_per_s", "tpot_p95_ms.saturated"}
    by = {m["name"]: m for m in bench["end_to_end"]}
    assert (by["tokens_per_s"]["bound"], by["tpot_p95_ms.saturated"]["bound"],
            by["setup_s"]["bound"]) == (0.03, 0.04, 0.1)
    # every listed metric moves an end-to-end metric the cell reports
    moved = {m["moves"] for m in bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert moved <= {"tokens_per_s", "tpot_p95_ms.saturated"}


def test_every_new_name_resolves():
    c = manifest.cell(CELL)
    p = c["params"]
    assert c["cell_file"]["params"]["clients"] == p["clients"] == 18
    assert p["generator"] == "closed_loop"
    assert p["prompt_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.5, "min": 1024,
        "max": 8192}
    assert p["max_tokens"] == {"dist": "uniform", "min": 256, "max": 384}
    assert (p["temperature"], p["pool_seed"], p["warm_seconds"]) == (
        1.0, 24, 12)
    # inherited from the traffic file: the single-row with-history rungs
    assert p["warm_prompt_tokens"] == [522, 536, 568, 632, 760]
    assert p["trace_seconds"] == 3
    notes = " ".join(p["notes"])
    assert "18 callers over 12 decode slots" in notes
    assert "8192 + 384 = 8576" in notes and "8704" in notes
    assert "64 decode slots" not in notes and "2560" not in notes
    for key in ("users", "exercises", "bypasses"):
        assert c["cell_file"][key]
    assert "code assistant" in c["cell_file"]["users"]
    assert "1.5 rows an expert" in c["cell_file"]["exercises"]
    assert os.path.isfile(c["profile_template"])
    assert os.path.isfile(os.path.join(ROOT, c["config"]["reference"]))
    readers = {m["name"]: m["reader"] for m in c["per_layer"]}
    assert readers["kernel.window_attn_share.8k"]["op"] == "^window_"
    assert readers["step.chunk_ms.window.8k"]["program"] == (
        "^jit_step_fn_t512_r1(_h)?\\(")
    assert "whole_op" not in readers["step.chunk_ms.window.8k"]
    assert readers["step.decode_ms.window"]["program"] == "^jit_step_fn_t0"
    from benchmark.lib.readers import READERS

    for name, spec in readers.items():
        assert spec["reduction"] in READERS, name
    srv = c["config"]["serving"]
    assert 8192 + 384 <= srv["max_context_tokens"] == (
        srv["max_pages_per_seq"] * srv["page_size"])
    assert p["clients"] == srv["max_decode_batch"] * 3 // 2
    assert {m["name"] for m in c["end_to_end"]} == {
        "tokens_per_s", "tpot_p95_ms.saturated", "setup_s"}


def test_the_pools_sizes_are_the_issues():
    """The schedule every run offers (``pool_seed`` 24): every prompt is 2 to
    17 chunks and no request passes 8,576 tokens."""
    from benchmark.lib import lengths

    c = manifest.cell(CELL)
    pool = lengths.pool(c["params"], 1024)
    prompts = [p for p, _ in pool]
    assert min(prompts) >= 1024 and max(prompts) <= 8192
    assert max(p + m for p, m in pool) <= 8576
    chunks = [-(-p // 512) for p in prompts]
    assert min(chunks) >= 2 and max(chunks) <= 16 + 1
    median = sorted(prompts)[len(prompts) // 2]
    assert 3800 < median < 4400
    assert all(256 <= m <= 384 for _, m in pool)


def test_the_readers_read_a_trace_without_the_programs_as_nothing():
    """On a synthetic summary: the chunk programs' mean, the decode-only
    program's time a MODEL step (two grouped products a layer and step), the
    window kernel's share of busy time; a capture without them (the parent's
    on another cell, or another model's) reads nothing and raises nothing."""
    from benchmark.lib.readers import READERS

    c = manifest.cell(CELL)
    readers = {m["name"]: m["reader"] for m in c["per_layer"]}
    dev = {"busy_s": 2.0, "modules": [
        # a fused window of four steps: 4 x 28 x 2 grouped products
        {"name": "jit_step_fn_t0(1)", "dur_s": 0.072,
         "ops": {"window_attention_tpu": 84, "grouped_matmul_tpu": 112,
                 "grouped_matmul_tpu.1": 112}},
        # one cut by the capture's edge: not a whole number of layers
        {"name": "jit_step_fn_t0(1)", "dur_s": 0.010,
         "ops": {"grouped_matmul_tpu": 13}},
        {"name": "jit_step_fn_t512_r1(2)", "dur_s": 0.050,
         "ops": {"window_attention_tpu": 21, "grouped_matmul_tpu": 56}},
        {"name": "jit_step_fn_t512_r1_h(3)", "dur_s": 0.060,
         "ops": {"window_attention_tpu": 42, "grouped_matmul_tpu": 56}},
        {"name": "jit_step_fn_t256_r1_h(4)", "dur_s": 0.030,
         "ops": {"window_attention_tpu": 42}}],
        "ops": {"window_attention_tpu": [90, 0.15],
                "window_attention_tpu.1": [60, 0.05], "fusion.1": [5, 1.0]}}
    ctx = {"trace": {"devices": [dev], "window_s": 3.0},
           "config": c["config"]}
    want = {"step.chunk_ms.window.8k": 55.0, "step.decode_ms.window": 18.0,
            "kernel.window_attn_share.8k": 10.0}
    for name, value in want.items():
        spec = readers[name]
        assert READERS[spec["reduction"]](ctx, spec) == pytest.approx(
            value), name
    bare = {"busy_s": 2.0, "ops": {"fusion.1": [5, 1.0]}, "modules": [
        {"name": "jit_step_fn_t64_r12(1)", "dur_s": 0.09,
         "ops": {"fusion": 3}}]}
    ctx = {"trace": {"devices": [bare], "window_s": 3.0},
           "config": c["config"]}
    for name in want:
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
        "mellum_reference", os.path.join(ROOT, cfg["reference"]))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.CONFIG == cfg
    L = 8
    rope = {k: dict(v) for k, v in cfg["rope_parameters"].items()}
    rope["full_attention"].update(original_max_position_embeddings=32)
    small = dict(
        cfg, vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=8,
        num_key_value_heads=2, head_dim=16, num_experts_per_tok=2,
        num_experts=8, sliding_window=8, num_hidden_layers=L,
        layer_types=cfg["layer_types"][:L],
        mlp_layer_types=cfg["mlp_layer_types"][:L], rope_parameters=rope)
    model = dataclasses.replace(
        ModelConfig.from_hf_config(small, name="small"), dtype="float32")
    params = init_params(model, jax.random.PRNGKey(5))
    toks = jnp.asarray(np.random.default_rng(5).integers(1, 256, size=70))
    got, _ = forward(params, model, toks[None], jnp.arange(70)[None],
                     attn_fn=prefill_attn_fn)
    want = np.asarray(mod.forward(params, small, toks))
    assert np.abs(np.asarray(got[0]) - want).max() < 1e-5
    assert want.std() > 0.05
