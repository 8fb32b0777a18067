"""The new configuration's yardstick (ISSUE 34): the operations-and-bytes
functions of ``benchmark/lib/model_bytes_retention.py`` against hand counts
and against what the program allocates, the configuration file against the
published config and its cut, the cell's listing, and the plain reference
beside it against the program's forward pass at a small size on the CPU."""

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
from benchmark.lib import model_bytes_retention as mb  # noqa: E402

NAME = "brumby-14b-int8"
CELL = "brumby-14b.saturated-long"


def config():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


PUBLISHED = {
    "hidden_size": 5120, "num_attention_heads": 40, "num_key_value_heads": 8,
    "head_dim": 128, "intermediate_size": 17408, "vocab_size": 151936,
    "rope_theta": 1000000, "rms_norm_eps": 1e-06, "hidden_act": "silu",
    "max_position_embeddings": 32768, "tie_word_embeddings": False,
    "attention_bias": False, "model_type": "brumby",
}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_width_is_as_published(key):
    assert config()[key] == PUBLISHED[key]


def test_the_cut_is_depth_only_and_stated():
    cfg = config()
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert (cfg["num_hidden_layers"], cfg["published_num_hidden_layers"]) == (
        10, 40)
    for word in ("one of four pipeline stages", "ten whole layers",
                 "no layer divided", "float32 retention state"):
        assert word in cfg["deployment"], word
    assumed = " ".join(cfg["assumed"])
    for word in ("retention_degree 2", "gate a kv head", "RMSNorm on q and k",
                 "1 / sqrt(128)", "eps 1e-6", "float32 retention state",
                 "D_held 8704", "uniform in [3, 7]", "state form alone"):
        assert word in assumed, word
    srv = cfg["serving"]
    assert (srv["num_pages"], srv["max_decode_batch"], srv["page_size"],
            srv["max_prefill_len"], srv["max_context_tokens"]) == (
        4096, 24, 16, 512, 2560)
    assert srv["kv_bytes_per_token"] == mb.kv_bytes_per_token(cfg) == 0
    assert srv["state_bytes_per_slot"] == mb.state_bytes_per_slot(cfg)
    profile = open(os.path.join(ROOT, cfg["profile"])).read()
    for size in ("num_layers: 10", "head_dim: 128", "num_kv_heads: 8",
                 "intermediate_size: 17408", "max_pages_per_seq: 160",
                 "num_pages: 4096", "max_decode_batch: 24",
                 "retention_degree: 2", "enable_prefix_cache: false",
                 "__SEED__"):
        assert size in profile, size


def test_catalog_keys_are_copied_whole():
    """Against the guide's catalog row, where the sandbox has it."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(path))
               if r["name"] == "Brumby-14B-Base")
    cfg = config()
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k
    assert row["config"]["num_hidden_layers"] == cfg[
        "published_num_hidden_layers"]


def test_the_profile_builds_the_catalog_model_cut_to_ten_layers():
    """``model_overrides`` restates the catalog entry at the cut depth, and
    the configuration file's Hugging Face keys give the same model."""
    import dataclasses

    import yaml

    from helix_tpu.models.common import BRUMBY_14B, ModelConfig

    cfg = config()
    with open(os.path.join(ROOT, cfg["profile"])) as f:
        prof = yaml.safe_load(f.read().replace("__SEED__", "7"))
    over = dict(prof["models"][0]["model_overrides"])
    over["layer_types"] = tuple(over["layer_types"])
    assert prof["models"][0]["name"] == cfg["model"] == BRUMBY_14B.name
    served = dataclasses.replace(BRUMBY_14B, **over)
    assert served == dataclasses.replace(
        BRUMBY_14B, num_layers=10, layer_types=("retention",) * 10)
    assert ModelConfig.from_hf_config(cfg, name=cfg["model"]) == served
    eng = prof["models"][0]["engine"]
    assert set(eng) == {"max_decode_batch", "page_size", "max_prefill_len",
                        "kv_cache_dtype", "num_pages", "max_pages_per_seq",
                        "enable_prefix_cache"}
    assert eng["enable_prefix_cache"] is False


def test_parameter_count_against_the_issues_hand_count():
    p = mb.parameter_count(config())
    layer = (2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408)
    assert abs(layer / 1e6 - 330.3) < 0.05
    assert p["dense_mlp"] == 10 * 3 * 5120 * 17408
    assert p["retention_operators"] == 10 * (
        2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 8 + 2 * 128)
    assert p["embedding"] == p["head"] == 151936 * 5120
    assert abs((p["embedding"] + p["head"]) / 1e9 - 1.556) < 0.001
    assert abs(p["total"] / 1e9 - 4.86) < 0.005


def test_weight_and_state_bytes_are_what_the_program_allocates():
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
    assert held == parts["total"] == mb.weight_bytes(cfg)
    assert abs(parts["total"] / 1e9 - 4.863) < 0.001
    srv = cfg["serving"]
    cc = CacheConfig(num_pages=srv["num_pages"], page_size=16,
                     state_slots=srv["max_decode_batch"])
    assert cc.page_bytes(model) == 0
    assert cc.state_shape(model)[-2] == srv["retention_state_rows"] == 8704
    assert cc.state_bytes(model) == cc.total_bytes(model) == (
        24 * mb.state_bytes_per_slot(cfg))
    assert mb.state_bytes_per_slot(cfg) == 10 * mb.state_bytes_per_slot_layer(
        cfg) == 10 * 8 * (8704 + 128) * 128 * 4
    # the notes' bytes are these, within 1%
    notes = " ".join(cfg["notes"])
    for said, got in (("4.863 GB", parts["total"] / 1e9),
                      ("8.682 GB", cc.state_bytes(model) / 1e9),
                      ("8.556 GB", 24 * 10 * 8 * 8704 * 128 * 4 / 1e9),
                      ("21.45 GB", mb.decode_step_bytes(cfg, 24) / 1e9)):
        assert said in notes and abs(float(said.split()[0]) / got - 1) < 0.01


def test_decode_step_and_kernel_calls_count_what_the_algorithm_needs():
    cfg = config()
    # a decode step: the matrices but the embedding table, and each live
    # row's state read once and written once in every layer
    full = mb.decode_step_bytes(cfg, 24)
    assert full - mb.decode_step_bytes(cfg, 23) == (
        2 * mb.state_bytes_per_slot(cfg) + 5120)
    assert abs(full / 1e9 - 21.45) < 0.01
    ops, bytes_ = mb.retention_decode_call(cfg, 24)
    entries = 24 * 8 * 8704 * 128
    assert ops == entries * (4 + 2 * 5)
    assert 2 * entries * 4 < bytes_ < 2 * entries * 4 * 1.02
    # the bytes bound it, far: 2.1 ms at 819 GB/s, 15 us at 197 TFLOP/s
    assert bytes_ / 819e9 > 100 * ops / 197e12
    # a 512-token chunk that continues from a state: phi(Q) S is most of it
    ops, bytes_ = mb.retention_chunk_call(cfg, 512)
    assert ops == (2 * 40 * (512 * 513 / 2) * 2 * 128
                   + 2 * 512 * 8 * 8704 * 128 + 2 * 512 * 40 * 8704 * 128)
    cold_ops, cold_bytes = mb.retention_chunk_call(cfg, 512, False)
    assert cold_ops == ops - 2 * 512 * 40 * 8704 * 128
    assert bytes_ - cold_bytes == mb.state_bytes_per_slot_layer(cfg)
    share, bound = mb.roofline_share(
        ops, bytes_, 1e-3, {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "flops" and abs(share - 100 * ops / 197e12 / 1e-3) < 1e-9


def test_the_reference_beside_the_configuration_is_the_programs_forward():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import forward, init_params, prefill_attn_fn

    path = os.path.join(ROOT, "benchmark", "configs", NAME + ".reference.py")
    spec = importlib.util.spec_from_file_location("ref_brumby", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    assert ref.CONFIG["name"] == NAME and ref.CONFIG["hidden_size"] == 5120
    small = dict(ref.CONFIG, vocab_size=300, hidden_size=64,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 intermediate_size=96, num_hidden_layers=2)
    cfg = dataclasses.replace(
        ModelConfig.from_hf_config(small), dtype="float32")
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 300, size=24), jnp.int32)
    for int8 in (False, True):
        params = init_params(cfg, jax.random.PRNGKey(2), int8=int8)
        with jax.default_matmul_precision("highest"):
            got, _ = forward(params, cfg, toks[None], jnp.arange(24)[None],
                             attn_fn=prefill_attn_fn)
        want = ref.forward(params, small, toks)
        assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-4


def test_the_cell_is_listed_where_the_issue_says():
    """That it IS listed under each metric the issue names; what else lists
    it is a later PR's to add (PERF.md section 7, item 23)."""
    bench = manifest.benchmark_json()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "saturated-long", 1)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= {
        "sched.slot_occupancy", "device.idle_share.saturated",
        "loop.host_build_ms.saturated", "loop.exposed_host_ms.saturated",
        "loop.admit_ms.saturated", "loop.prefill_sync_ms.saturated",
        "loop.dispatch_ms.saturated", "loop.fetch_ms.saturated",
        "loop.reconcile_ms.saturated", "loop.emit_ms.saturated",
        "loop.deliver_ms.saturated", "loop.emit_queue_wait_ms.saturated",
        "kernel.retention_share", "step.decode_ms.retention",
        "step.chunk_ms.retention"}
    # it runs none of these operations
    assert not listed & {
        "kernel.attn_share.saturated", "kernel.attn_share.chat",
        "kernel.mla_share", "kernel.grouped_mm_share", "kernel.moe_share",
        "step.decode_ms", "step.decode_hbm_share"}
    assert {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", ())} >= {
        "tokens_per_s", "tpot_p95_ms.saturated"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
    assert len(bench["workloads"]) == 7 and len(bench["configs"]) == 5


def test_every_new_name_resolves():
    c = manifest.cell(CELL)
    assert c["params"]["clients"] == 36          # the cell's one override
    assert c["params"]["generator"] == "closed_loop"
    assert c["params"]["prompt_tokens"]["median"] == 1024
    assert c["cell_file"]["params"] == {"clients": 36}
    assert os.path.isfile(c["profile_template"])
    assert os.path.isfile(os.path.join(ROOT, c["config"]["reference"]))
    readers = {m["name"]: m["reader"] for m in c["per_layer"]}
    assert readers["kernel.retention_share"]["op"] == "^retention_"
    decode = readers["step.decode_ms.retention"]
    assert (decode["program"], decode["per_op"], decode["whole_op"]) == (
        "^jit_step_fn_t0", "^retention_decode_tpu", "^retention_decode_tpu")
    assert readers["step.chunk_ms.retention"]["program"] == (
        "^jit_step_fn_t512_r1(_h)?\\(")
    # a request fits a sequence's pages, and every slot's fit the table
    srv = c["config"]["serving"]
    assert 2048 + 384 <= srv["max_context_tokens"] == 160 * srv["page_size"]
    assert srv["max_decode_batch"] * 160 + 1 <= srv["num_pages"]
