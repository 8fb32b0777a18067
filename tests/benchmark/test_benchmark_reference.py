"""The benchmark's plain reference against the program's forward pass, at a
small size on the CPU, at both configurations' geometry in miniature (a
query group of 7 with attention bias; a group of 4 without).  On the chip
the server exposes no logits, so this is where the reference is held to the
program (PERF.md, Open questions)."""

import importlib.util
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


@pytest.mark.parametrize("heads,kv_heads,bias", [(7, 1, True), (8, 2, False)],
                         ids=["qwen2-like", "mistral-like"])
def test_reference_agrees_with_the_programs_forward(heads, kv_heads, bias):
    import jax
    import jax.numpy as jnp

    from benchmark.lib.reference_dense_decoder import forward as reference
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import forward, init_params, prefill_attn_fn

    cfg = ModelConfig.tiny(
        num_heads=heads, num_kv_heads=kv_heads, head_dim=16,
        hidden_size=heads * 16, attention_bias=bias, rope_theta=1e6,
        rms_norm_eps=1e-6, dtype="float32", vocab_size=300,
        intermediate_size=96)
    params = init_params(cfg, jax.random.PRNGKey(1))
    if bias:        # init_params draws zero biases: make them count
        for i, name in enumerate(("wq", "wk", "wv")):
            b = params["layers"][name]["bias"]
            params["layers"][name]["bias"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(5 + i), b.shape)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 300, size=(1, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, tokens, jnp.arange(24)[None],
                         attn_fn=prefill_attn_fn)
    hf = {"num_attention_heads": heads, "num_key_value_heads": kv_heads,
          "head_dim": 16, "hidden_size": heads * 16, "rms_norm_eps": 1e-6,
          "rope_theta": 1e6, "num_hidden_layers": cfg.num_layers}
    want = reference(params, hf, tokens[0])
    # float32 both sides, same mathematics: rounding only.  Computing in
    # bf16 would miss this by three orders of magnitude.
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-5


@pytest.mark.parametrize("name", ["qwen2-7b-int8", "mistral-7b-v03-int8"])
def test_each_configuration_has_its_reference_beside_it(name):
    path = os.path.join(ROOT, "benchmark", "configs", name + ".reference.py")
    spec = importlib.util.spec_from_file_location("ref_" + name[:5], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.forward)
    assert mod.CONFIG["name"] == name
    assert mod.CONFIG["hidden_size"] in (3584, 4096)
