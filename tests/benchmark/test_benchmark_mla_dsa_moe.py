"""The new configuration's yardstick (ISSUE 53): the operations-and-bytes
functions of ``benchmark/lib/model_bytes_mla_dsa_moe.py`` against hand counts
and against what the program allocates at the cut, the configuration file
against the published config and its cut, the cell's listing (by name, never
by position), the new metric files' reductions, and the plain reference beside
it against the program's forward pass at a small size on the CPU.
(``step.decode_ms.dsa``, which ISSUE 53 also names, is not here: in this cell
no decode-only program falls inside a 3 s capture, so its reader would find
nothing: PERF.md section 6, PR 53.)"""

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
from benchmark.lib import model_bytes_mla_dsa_moe as mb  # noqa: E402
from benchmark.lib.readers import READERS  # noqa: E402

NAME = "glm-5-int8"
CELL = "glm-5.saturated-16k"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "num_nextn_predict_layers"]
NEW_METRICS = {"kernel.dsa_index_share": "tpot_p95_ms.saturated",
               "kernel.mla_sparse_share": "tpot_p95_ms.saturated",
               "step.chunk_ms.dsa": "tokens_per_s"}


def config():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


PUBLISHED = {
    "hidden_size": 6144, "intermediate_size": 12288,
    "moe_intermediate_size": 2048, "num_attention_heads": 64,
    "num_key_value_heads": 64, "head_dim": 64, "kv_lora_rank": 512,
    "q_lora_rank": 2048, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "qk_head_dim": 256, "index_n_heads": 32,
    "index_head_dim": 128, "index_topk": 2048,
    "indexer_rope_interleave": True, "num_experts_per_tok": 8,
    "n_shared_experts": 1, "vocab_size": 154880,
    "routed_scaling_factor": 2.5, "max_position_embeddings": 202752,
    "model_type": "glm_moe_dsa", "tie_word_embeddings": False,
    "topk_method": "noaux_tc", "n_group": 1, "scoring_func": "sigmoid",
    "rms_norm_eps": 1e-05,
}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_width_is_as_published(key):
    assert config()[key] == PUBLISHED[key]


def test_the_cut_is_stated_key_by_key():
    cfg = config()
    assert cfg["reduced"] == REDUCED
    assert cfg["rope_parameters"] == {"rope_theta": 1000000,
                                      "rope_type": "default"}
    for key, (cut, published) in {
            "num_hidden_layers": (8, 78), "first_k_dense_replace": (1, 3),
            "n_routed_experts": (16, 256),
            "num_nextn_predict_layers": (0, 1)}.items():
        assert (cfg[key], cfg["published_" + key]) == (cut, published), key
    assert cfg["held_experts"] == [0, 16]
    assert cfg["kept_published_layers"] == [0, 3, 4, 5, 6, 7, 8, 9]
    for word in ("16 chips share each layer's routed experts",
                 "expert parallel", "data-parallel", "pipeline stages",
                 "rank 0 of 16", "index-key pool"):
        assert word in cfg["deployment"], word
    assumed = " ".join(cfg["assumed"])
    for word in ("DeepSeek Sparse Attention", "LayerNorm", "eps 1e-6",
                 "FIRST qk_rope_head_dim 64 dims", "32 ** -0.5 * 128 ** -0.5",
                 "ties to the smaller s", "FP8", "Hadamard", "noaux_tc",
                 "multi-token prediction", "1e-6 is this repo's",
                 "seeded weights"):
        assert word in assumed, word
    srv = cfg["serving"]
    assert (srv["num_pages"], srv["max_decode_batch"], srv["page_size"],
            srv["max_prefill_len"], srv["max_pages_per_seq"],
            srv["max_context_tokens"], srv["enable_prefix_cache"]) == (
        16897, 16, 16, 512, 1056, 16896, False)
    assert srv["index_key_bytes_per_token_layer"] == (
        mb.index_key_bytes_per_token_layer(cfg)) == 256
    assert srv["latent_bytes_per_token_layer"] == (
        mb.latent_bytes_per_token_layer(cfg)) == 1280
    profile = open(os.path.join(ROOT, cfg["profile"])).read()
    for size in ("num_layers: 8", "held_experts: [0, 16]",
                 "num_experts: 256", "q_lora_rank: 2048", "index_heads: 32",
                 "index_topk: 2048", "max_pages_per_seq: 1056",
                 "num_pages: 16897", "max_decode_batch: 16",
                 "enable_prefix_cache: false", "__SEED__"):
        assert size in profile, size


def test_catalog_keys_are_copied_whole():
    """Against the guide's catalog row, where the sandbox has it."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(path)) if r["name"] == "GLM-5")
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

    from helix_tpu.models.common import CATALOG, ModelConfig

    cfg = config()
    glm = CATALOG["zai-org/GLM-5"]
    with open(os.path.join(ROOT, cfg["profile"])) as f:
        prof = yaml.safe_load(f.read().replace("__SEED__", "7"))
    over = dict(prof["models"][0]["model_overrides"])
    over["held_experts"] = tuple(over["held_experts"])
    assert prof["models"][0]["name"] == cfg["model"] == glm.name
    served = dataclasses.replace(glm, **over)
    assert served == dataclasses.replace(
        glm, num_layers=8, first_k_dense=1, held_experts=(0, 16))
    assert ModelConfig.from_hf_config(cfg, name=cfg["model"]) == served
    eng = prof["models"][0]["engine"]
    assert set(eng) == {"max_decode_batch", "page_size", "max_prefill_len",
                        "kv_cache_dtype", "num_pages", "max_pages_per_seq",
                        "enable_prefix_cache"}
    assert eng["num_pages"] == 16 * eng["max_pages_per_seq"] + 1


def test_parameter_count_against_the_issues_hand_count():
    p = mb.parameter_count(config())
    assert p["latent_mixers"] == 8 * 165_019_648
    assert p["indexers"] == 8 * 9_371_648
    assert p["dense_mlp"] == 226_492_416
    assert p["held_experts"] == 7 * 16 * 37_748_736
    assert p["shared_experts"] == 7 * 37_748_736
    assert p["routers"] == 7 * 1_572_864
    assert p["embedding"] + p["head"] == 1_903_165_440
    assert p["total"] - p["vectors"] == 400_883_712 + 7 * 817_692_672 + (
        1_903_165_440) == 8_027_897_856


def test_bytes_are_what_the_program_allocates_at_the_cut():
    """Weights and BOTH pools, byte for byte against ``init_params(int8=
    True)`` and ``CacheConfig`` (shapes only: nothing is allocated), and
    within 1% of the issue's 8.03 / 3.32 GB."""
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
    assert tree["layers"]["experts"]["w_gate"]["weight"].shape == (
        7, 16, 6144, 2048)
    assert tree["layers"]["router"]["weight"].shape == (7, 6144, 256)
    assert tree["layers"]["wq_idx"]["weight"].shape == (7, 2048, 4096)
    srv = cfg["serving"]
    cc = CacheConfig(num_pages=srv["num_pages"], page_size=srv["page_size"],
                     max_pages_per_seq=srv["max_pages_per_seq"])
    assert cc.page_bytes(model) == mb.page_bytes(cfg, 16) == 196_608
    assert mb.token_bytes(cfg) == (8 * 1280, 8 * 256)
    assert cc.page_shapes(model) == ((8, 16, 640), (8, 16, 128))
    assert cc.max_seq_len == srv["max_context_tokens"] == 16896
    for got, issue in ((allocated, 8.03e9),
                       (cc.total_bytes(model), 3.32e9)):
        assert abs(got / issue - 1) < 0.01, (got, issue)


def test_a_decode_step_and_the_kernels_calls_by_hand():
    cfg = config()
    rows = [16768] * 16
    sparse = mb.decode_step_bytes(cfg, rows)
    dense = mb.decode_step_bytes(cfg, rows, every_latent=True)
    # a row at 16,768 keys: 256 B x 16,768 of index keys + 1,280 B x 2,048 of
    # latent rows a layer (6.9 MB) where every latent read is 21.5 MB
    per_row = 8 * (16768 * 256 + 2048 * 1280)
    assert sparse - (dense - 16 * 8 * 16768 * 1280) == 16 * per_row
    assert abs(8 * 16768 * 1280 / 1e6 / 8 - 21.46) < 0.01
    assert abs(per_row / 8 / 1e6 - 6.91) < 0.01
    assert dense - sparse == 16 * 8 * (16768 * 1280 - 16768 * 256
                                       - 2048 * 1280)
    # under index_topk keys a row reads all it has, and its index keys too
    assert mb.decode_step_bytes(cfg, [100]) - mb.decode_step_bytes(
        cfg, [100], every_latent=True) == 100 * 8 * 256
    ops, b = mb.index_scores_call(cfg, 1, 16896, rows=16)
    assert ops == 16 * 16896 * 32 * (2 * 128 + 3)
    assert b == 16 * 16896 * 256 + 16 * 32 * (256 + 4) + 16 * 16896 * 4
    ops, b = mb.index_scores_call(cfg, 512, 16896)
    assert ops == 512 * 16896 * 32 * 259                   # 71.7 G
    ops, b = mb.sparse_mla_call(cfg, 1, 2048, rows=16)
    assert ops == 2 * 16 * 2048 * 64 * (640 + 512)
    assert b == 16 * 2048 * 1280 + 16 * 64 * 1152 * 2 + 16 * 2048 * 4
    ops, b = mb.sparse_mla_call(cfg, 512, 16896 + 512)
    assert ops == 2 * 512 * 17408 * 64 * 1152              # 1.31 T
    ops, b = mb.select_call(cfg, 512, 17408)
    assert b == 34 * 512 * 17408 * 4
    from benchmark.lib.peaks import chip_peaks

    assert mb.roofline_share(197e12, 0, 1.0, chip_peaks("TPU v5 lite")) == (
        100.0, "flops")


def test_the_cell_is_listed_by_name():
    """By name under each metric the issue names, whatever its place."""
    bench = manifest.benchmark_json()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "saturated-long", 1)
    assert len(bench["workloads"]) >= 12 and len(bench["configs"]) >= 10
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == REDUCED
    assert entry["source"] == config()["source"]
    listed = {m["name"]: m for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(listed) >= set(NEW_METRICS) | {
        "device.idle_share.saturated", "loop.host_build_ms.saturated",
        "loop.dispatch_ms.saturated", "kernel.grouped_mm_share"}
    for name, moves in NEW_METRICS.items():
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == moves
    # it runs none of these operations
    assert not set(listed) & {
        "kernel.attn_share.saturated", "kernel.attn_share.chat",
        "kernel.moe_share", "step.decode_ms", "step.decode_hbm_share",
        "kernel.window_attn_share", "kernel.deltanet_share"}
    assert {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", ())} == {
        "tokens_per_s", "tpot_p95_ms.saturated"}


def test_the_traffic_is_the_issues_letter_for_letter():
    cell = manifest.cell(CELL)
    p = cell["params"]
    assert (p["generator"], p["clients"], p["temperature"], p["pool_seed"],
            p["warm_seconds"]) == ("closed_loop", 24, 1.0, 24, 30)
    assert p["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                  "sigma": 0.5, "min": 4096, "max": 16384}
    assert p["max_tokens"] == {"dist": "uniform", "min": 256, "max": 384}
    assert p["warm_prompt_tokens"] == [522, 536, 568, 632, 760]
    srv = cell["config"]["serving"]
    assert (p["prompt_tokens"]["max"] + p["max_tokens"]["max"]
            <= srv["max_context_tokens"])
    for key in ("users", "exercises", "bypasses"):
        assert cell["cell_file"][key]
    assert "other 15" in cell["cell_file"]["bypasses"]
    plan = cell["generator"].plan(p, 123456789012, 45.0)
    assert plan


def _trace(programs):
    ops = {}
    for m in programs:
        for n, c in m["ops"].items():
            calls = ops.get(n, [0, 0])[0] + c
            ops[n] = [calls, 0.001 * calls]
    return {"devices": [{"busy_s": 1.0, "ops": ops, "modules": programs,
                         "gaps": []}], "window_s": 3.0}


def test_the_new_metrics_read_a_trace_and_nothing_from_a_parents():
    """Each new metric's file under the reduction it names: a capture of this
    program (8 layers; a fused window of 4 steps, a chunk with history, a
    cold chunk) and a parent's capture, which has none of the kernels."""
    cell = manifest.cell(CELL)
    readers = {m["name"]: m["reader"] for m in cell["per_layer"]}
    window = {"name": "jit_step_fn_t0(123)", "dur_s": 0.048, "ops": {
        "mla_sparse_attention_tpu": 32, "dsa_index_scores_tpu": 32,
        "grouped_matmul_tpu": 56}}
    cut = {"name": "jit_step_fn_t0(124)", "dur_s": 0.01, "ops": {
        "mla_sparse_attention_tpu": 5, "dsa_index_scores_tpu": 5}}
    chunk = {"name": "jit_step_fn_t512_r1_h(9)", "dur_s": 0.060, "ops": {
        "mla_sparse_attention_tpu": 16, "dsa_index_scores_tpu": 24}}
    cold = {"name": "jit_step_fn_t512_r1(8)", "dur_s": 0.040, "ops": {
        "mla_sparse_attention_tpu": 8, "dsa_index_scores_tpu": 8,
        "mla_ragged_paged_attention_tpu": 8}}
    ctx = {"trace": _trace([window, cut, chunk, cold]),
           "config": cell["config"]}

    def read(name, c=ctx):
        spec = readers[name]
        return READERS[spec["reduction"]](c, spec)

    assert read("step.chunk_ms.dsa") == pytest.approx(50.0)
    assert read("kernel.dsa_index_share") == pytest.approx(
        100 * 0.001 * 69)
    assert read("kernel.mla_sparse_share") == pytest.approx(
        100 * 0.001 * 61)
    parent = {"trace": _trace([{
        "name": "jit_step_fn_t0(1)", "dur_s": 0.02,
        "ops": {"mla_ragged_paged_attention_tpu": 16}}]),
        "config": cell["config"]}
    for name in NEW_METRICS:
        assert read(name, parent) is None, name
        assert read(name, {"trace": None, "config": cell["config"]}) is None


def test_the_reference_file_loads_by_path_and_agrees_with_the_program():
    """``glm-5-int8.reference.py`` loaded as the harness would, at a small
    size on the CPU against the program's plain forward pass (float32 both
    sides: the same scores, the same choice)."""
    import functools

    import jax
    import jax.numpy as jnp

    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import forward, init_params, prefill_attn_fn

    path = os.path.join(ROOT, "benchmark", "configs", NAME + ".reference.py")
    spec = importlib.util.spec_from_file_location("glm5_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.CONFIG == config()
    hf = dict(
        config(), vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=4, kv_lora_rank=32, q_lora_rank=24,
        qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
        num_hidden_layers=3, index_n_heads=4, index_head_dim=16,
        index_topk=24, num_experts_per_tok=3, n_routed_experts=4,
        published_n_routed_experts=16, held_experts=[4, 8])
    cfg = ModelConfig.from_hf_config(hf, name="tiny")
    import dataclasses
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 256, 60))
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, toks[None], jnp.arange(60)[None],
                         attn_fn=functools.partial(prefill_attn_fn, cfg=cfg))
    want, scores, sets = mod.forward(params, hf, toks, want="index")
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 5e-5
    assert np.asarray(sets[0]).sum(-1).max() == 24
    on_own = mod.forward(params, hf, toks, selection=[np.asarray(s)
                                                      for s in sets])
    assert np.abs(np.asarray(on_own) - np.asarray(want)).max() < 1e-6
    blocks = mod.forward(params, hf, toks, layers=(2, 3),
                         h=mod.forward(params, hf, toks, layers=(0, 2),
                                       head=False))
    assert np.abs(np.asarray(blocks) - np.asarray(want)).max() < 1e-6
