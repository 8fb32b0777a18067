"""Llama-family decoder as functional JAX over a parameter pytree.

Covers Llama-3, Phi-3 (MHA, fused-free), Qwen-2 (attention bias), Qwen-3
(qk-norm) via ``ModelConfig`` switches — the decoder families the reference
serves through vLLM containers (``design/sample-profiles/``), here as owned
TPU-first code:

- Layers are **stacked** (every weight has a leading ``num_layers`` dim) and
  the forward pass is a single ``lax.scan`` — one layer gets traced/compiled
  once regardless of depth, keeping XLA compile times flat.
- Attention is injected (``attn_fn``) so the same forward serves training
  (flash attention), prefill (flash + segment masks) and decode (paged
  attention over the engine's KV cache) without re-tracing model code.
- All matmuls run in bf16 on the MXU with fp32 accumulation
  (``preferred_element_type``); norms/softmax in fp32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from helix_tpu.models.common import ModelConfig
from helix_tpu.ops.norms import rms_norm
from helix_tpu.ops.rope import apply_rope, rope_frequencies

Params = dict
# attn_fn(q, k, v, layer_cache, positions) -> attention output
AttnFn = Callable[..., jax.Array]


def _dense(x, p, adapter_ids=None):
    """x @ p["weight"] with fp32 MXU accumulation; handles int8-quantized
    weights ({weight, scale}), optional bias, and batched multi-LoRA
    pool slots (``adapter_ids`` names each token's slot) transparently."""
    from helix_tpu.ops.quant import maybe_dequant_dense

    return maybe_dequant_dense(x, p, adapter_ids=adapter_ids)


def _act(name: str):
    if name == "silu":
        return jax.nn.silu
    if name in ("gelu", "gelu_new", "gelu_pytorch_tanh", "gelu_tanh"):
        return functools.partial(jax.nn.gelu, approximate=True)
    raise ValueError(f"unknown activation {name}")


def _seeded_int8(key, shape, dtype, std, embed, shardings):
    """int8 codes + scales of a seeded normal tensor, never materialised
    in float: a stacked ``[L, ...]`` tensor is drawn and quantized one
    layer at a time inside one jit (Qwen2-7B's bf16 ``w_gate`` alone is
    3.8 GB, its f32 draw 7.6 GB, on a 16 GB chip)."""
    from helix_tpu.ops.quant import quantize_embedding, quantize_tensor

    quantize = quantize_embedding if embed else quantize_tensor

    def draw(k, shp):
        w = jax.random.normal(k, shp, jnp.float32) * std
        return quantize(w.astype(dtype))

    def build(k):
        if len(shape) == 2:
            return draw(k, shape)
        return jax.lax.map(
            lambda kk: draw(kk, shape[1:]), jax.random.split(k, shape[0])
        )

    # out_shardings is keyed like the output dict ({weight, scale});
    # lax.map stacks each layer's [1, out] scale row into [L, 1, out],
    # the shape quantize_tensor gives the stacked tensor
    return jax.jit(build, out_shardings=shardings)(key)


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype=None, *, int8: bool = False,
    shardings=None,
) -> Params:
    """Random-init a stacked-layer parameter tree (tests, training-from-init,
    seeded serving without a checkpoint).

    Real checkpoints come from ``helix_tpu.models.loader`` which produces the
    same tree from HF safetensors.

    ``int8=True`` returns the tree ``ops.quant.quantize_params`` would give
    (same structure, dtypes and scale shapes; a different draw from the
    same seed), built tensor by tensor so that no float copy of a matmul
    weight ever exists on the device.  ``shardings`` is then an optional
    tree of shardings matching that int8 tree (a mesh's
    ``quantized_logical_axes``); each tensor is born sharded.
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    L, E, H, KVH, D, F, V = (
        cfg.num_layers,
        cfg.hidden_size,
        cfg.num_heads,
        cfg.num_kv_heads,
        cfg.head_dim,
        cfg.intermediate_size,
        cfg.vocab_size,
    )
    ks = jax.random.split(key, 8)

    def weight(k, shape, *path, std=0.02):
        """One matmul weight's leaf dict, at ``path`` in the tree."""
        if not int8:
            w = jax.random.normal(k, shape, jnp.float32) * std
            return {"weight": w.astype(dtype)}
        sh = shardings
        for name in path if sh is not None else ():
            sh = sh[name]
        return _seeded_int8(k, shape, dtype, std, path == ("embed",), sh)

    params = {
        "embed": weight(ks[0], (V, E), "embed"),
        "layers": {
            "attn_norm": {"weight": jnp.ones((L, E), dtype)},
            "mlp_norm": {"weight": jnp.ones((L, E), dtype)},
            "wq": weight(ks[1], (L, E, H * D), "layers", "wq"),
            "wk": weight(ks[2], (L, E, KVH * D), "layers", "wk"),
            "wv": weight(ks[3], (L, E, KVH * D), "layers", "wv"),
            "wo": weight(ks[4], (L, H * D, E), "layers", "wo"),
            "w_gate": weight(ks[5], (L, E, F), "layers", "w_gate"),
            "w_up": weight(ks[6], (L, E, F), "layers", "w_up"),
            "w_down": weight(ks[7], (L, F, E), "layers", "w_down"),
        },
        "final_norm": {"weight": jnp.ones((E,), dtype)},
    }
    if cfg.num_experts > 0:
        # Mixtral-family: router + expert-stacked SwiGLU replaces the
        # dense FFN (models/moe.py)
        X = cfg.num_experts
        kk = jax.random.split(jax.random.fold_in(key, 7), 4)
        layers = params["layers"]
        del layers["w_gate"], layers["w_up"], layers["w_down"]
        layers["router"] = weight(kk[0], (L, E, X), "layers", "router")
        ex = ("layers", "experts")
        layers["experts"] = {
            "w_gate": weight(kk[1], (L, X, E, F), *ex, "w_gate"),
            "w_up": weight(kk[2], (L, X, E, F), *ex, "w_up"),
            "w_down": weight(kk[3], (L, X, F, E), *ex, "w_down"),
        }
    if cfg.attention_bias:
        for nm, width in (("wq", H * D), ("wk", KVH * D), ("wv", KVH * D)):
            params["layers"][nm]["bias"] = jnp.zeros((L, width), dtype)
    if cfg.qk_norm:
        params["layers"]["q_norm"] = {"weight": jnp.ones((L, D), dtype)}
        params["layers"]["k_norm"] = {"weight": jnp.ones((L, D), dtype)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = weight(
            jax.random.fold_in(key, 99), (E, V), "lm_head")
    if int8 and shardings is not None:
        # the matmul weights were born sharded; this places what is left
        # (norms, biases) and is a no-op for the rest
        params = jax.tree.map(jax.device_put, params, shardings)
    return params


def param_logical_axes(cfg: ModelConfig) -> Any:
    """Tree of logical-axis tuples matching ``init_params``.

    The leading stacked-layer axis carries the "layers" logical name on
    EVERY weight: it prunes to replicated on meshes without a pp axis,
    and shards layer blocks across pipeline groups on ``mesh: {pp: N}``
    — a new stacked weight must use "layers" too or it silently
    replicates across the pipeline."""
    lax_ = {
        "attn_norm": {"weight": ("layers", None)},
        "mlp_norm": {"weight": ("layers", None)},
        "wq": {"weight": ("layers", "embed", "heads")},
        "wk": {"weight": ("layers", "embed", "kv_heads")},
        "wv": {"weight": ("layers", "embed", "kv_heads")},
        "wo": {"weight": ("layers", "heads", "embed")},
        "w_gate": {"weight": ("layers", "embed", "mlp")},
        "w_up": {"weight": ("layers", "embed", "mlp")},
        "w_down": {"weight": ("layers", "mlp", "embed")},
    }
    if cfg.num_experts > 0:
        del lax_["w_gate"], lax_["w_up"], lax_["w_down"]
        lax_["router"] = {"weight": ("layers", "embed", None)}
        lax_["experts"] = {
            "w_gate": {"weight": ("layers", "expert", "embed", "mlp")},
            "w_up": {"weight": ("layers", "expert", "embed", "mlp")},
            "w_down": {"weight": ("layers", "expert", "mlp", "embed")},
        }
    if cfg.attention_bias:
        lax_["wq"]["bias"] = ("layers", "heads")
        lax_["wk"]["bias"] = ("layers", "kv_heads")
        lax_["wv"]["bias"] = ("layers", "kv_heads")
    if cfg.qk_norm:
        lax_["q_norm"] = {"weight": ("layers", None)}
        lax_["k_norm"] = {"weight": ("layers", None)}
    axes = {
        "embed": {"weight": ("vocab", "embed")},
        "layers": lax_,
        "final_norm": {"weight": (None,)},
    }
    if not cfg.tie_word_embeddings:
        axes["lm_head"] = {"weight": ("embed", "vocab")}
    return axes


def _layer(
    h,
    layer_params: Params,
    layer_cache,
    cfg: ModelConfig,
    positions,
    inv_freq,
    attn_fn: AttnFn,
    moe_token_mask=None,
    adapter_ids=None,
):
    """One decoder block. h: [B, S, E].

    When ``attn_fn`` returns ``(out, new_cache)`` (the carry-cache decode
    protocol — the paged pool threads through the layer scan and the
    kernel updates it in place), the new cache is returned as the third
    element; plain attn_fns (prefill) return the output alone.

    The fourth return is the layer's MoE capacity-overflow drop count
    (int32 scalar, 0 for dense layers) — threaded out of the scan so the
    engine can surface silently-dropped routing work in its stats.
    """
    B, S, E = h.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = layer_params

    # --- attention ---
    # the named scopes are what a profiler trace calls these operations,
    # whatever number the compiler gives their fusions
    with jax.named_scope("attn.qkv"):
        x = rms_norm(
            h, p["attn_norm"]["weight"], cfg.rms_norm_eps, cfg.norm_offset
        )
        q = _dense(x, p["wq"], adapter_ids).reshape(B, S, H, D)
        k = _dense(x, p["wk"], adapter_ids).reshape(B, S, KVH, D)
        v = _dense(x, p["wv"], adapter_ids).reshape(B, S, KVH, D)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"]["weight"], cfg.rms_norm_eps)
            k = rms_norm(k, p["k_norm"]["weight"], cfg.rms_norm_eps)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
    with jax.named_scope("attn.kernel"):
        res = attn_fn(q, k, v, layer_cache, positions)
    new_cache = None
    if isinstance(res, tuple):
        attn_out, new_cache = res
    else:
        attn_out = res
    with jax.named_scope("attn.out"):
        h = h + _dense(attn_out.reshape(B, S, H * D), p["wo"], adapter_ids)

    # --- mlp ---
    x = rms_norm(h, p["mlp_norm"]["weight"], cfg.rms_norm_eps, cfg.norm_offset)
    act = _act(cfg.hidden_act)
    moe_dropped = jnp.int32(0)
    if cfg.num_experts > 0:
        from helix_tpu.models.moe import moe_ffn

        router_w = p["router"]["weight"]
        if router_w.dtype == jnp.int8:
            # dequantise in fp32: the router's softmax runs in fp32, and
            # rounding through bf16 here could flip near-tied top-k picks
            router_w = router_w.astype(jnp.float32) * p["router"][
                "scale"
            ].astype(jnp.float32)
        moe_out, moe_dropped = moe_ffn(
            x, router_w, p["experts"], cfg, act,
            token_mask=moe_token_mask,
            return_dropped=True,
        )
        h = h + moe_out
    else:
        with jax.named_scope("mlp.gate_up"):
            gate = _dense(x, p["w_gate"], adapter_ids)
            up = _dense(x, p["w_up"], adapter_ids)
        with jax.named_scope("mlp.down"):
            h = h + _dense(act(gate) * up, p["w_down"], adapter_ids)
    return h, (k, v), new_cache, moe_dropped


def scan_decoder_blocks(
    h, layers_params, num_layers: int, block, layer_caches, carry_caches
):
    """Shared cache-protocol dispatch for decoder towers (llama families +
    the Qwen2-VL mrope tower share this so the two protocols cannot
    diverge).

    ``block(h, layer_params, layer_cache) -> (h, (k, v), new_cache,
    moe_dropped)``.

    - xs mode (``layer_caches`` or no cache): the scan slices a per-layer
      cache view; returns (h, kv, moe_dropped) with kv stacked [L, ...]
      for the caller's scatter.
    - carry mode (``carry_caches``): the full cache pytree threads through
      the scan carry and block's attn_fn receives ``(caches, layer_idx)``;
      returns (h, final_caches, moe_dropped).

    ``moe_dropped`` is the int32 total of MoE capacity-overflow drops
    summed over all layers (0 for dense towers).
    """
    if carry_caches is not None:
        def carry_body(carry, xs):
            h, caches, drops = carry
            layer_params, lyr = xs
            h, _, caches, d = block(h, layer_params, (caches, lyr))
            return (h, caches, drops + d), None

        xs = (layers_params, jnp.arange(num_layers, dtype=jnp.int32))
        (h, kv, dropped), _ = jax.lax.scan(
            carry_body, (h, carry_caches, jnp.int32(0)), xs
        )
    else:
        def scan_body(h, xs):
            layer_params, layer_cache = xs
            h, kv, _, d = block(h, layer_params, layer_cache)
            return h, (kv, d)

        if layer_caches is None:
            # lax.scan needs every xs leaf to have a leading L dim; "no
            # history" is a zero-length dummy the attn_fn never touches.
            layer_caches = jnp.zeros((num_layers, 0), jnp.int32)
        h, (kv, drops) = jax.lax.scan(
            scan_body, h, (layers_params, layer_caches)
        )
        dropped = jnp.sum(drops)
    return h, kv, dropped


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens,               # [B, S] int32
    positions,            # [B, S] int32 (absolute, ragged-aware)
    *,
    attn_fn: AttnFn,
    layer_caches=None,    # pytree whose leaves have leading num_layers dim
    carry_caches=None,    # pytree threaded through the scan as carry
    return_hidden: bool = False,
    moe_token_mask=None,  # [B, S] bool: MoE routing validity (padding /
                          # inactive decode slots never consume capacity)
    return_moe_stats: bool = False,  # also return {"dropped": int32} —
                          # MoE capacity-overflow drops summed over layers
    adapter_ids=None,     # [B, S] i32: per-token multi-LoRA pool slot
                          # (0 = identity); None = no batched adapters
):
    """Run the decoder.

    Two cache protocols:

    - ``layer_caches`` (prefill): the scan slices a per-layer view as xs;
      ``attn_fn(q, k, v, layer_cache, pos)`` returns the attention output;
      returns (logits, kv) with kv = fresh K/V stacked [L, B, S, KVH, D]
      for the caller's one-shot scatter into the paged pool.
    - ``carry_caches`` (decode): the FULL cache pytree threads through the
      scan carry; ``attn_fn(q, k, v, (caches, layer_idx), pos)`` returns
      ``(out, new_caches)`` and updates the pool itself (the Pallas kernel
      writes the token's K/V in place) — no stacked kv, no scatter, no
      pool-sized layout copies in the loop.  Returns (logits, caches).
    """
    from helix_tpu.ops.quant import embed_lookup

    inv_freq = jnp.asarray(
        rope_frequencies(cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
    )
    h = embed_lookup(params["embed"], tokens, jnp.dtype(cfg.dtype))

    def block(h, layer_params, layer_cache):
        return _layer(
            h, layer_params, layer_cache, cfg, positions, inv_freq,
            attn_fn, moe_token_mask=moe_token_mask,
            adapter_ids=adapter_ids,
        )

    h, kv, moe_dropped = scan_decoder_blocks(
        h, params["layers"], cfg.num_layers, block, layer_caches,
        carry_caches,
    )
    h = rms_norm(h, params["final_norm"]["weight"], cfg.rms_norm_eps, cfg.norm_offset)
    if return_hidden:
        return h, kv
    if cfg.tie_word_embeddings:
        w_out = params["embed"]["weight"].T
        out_scale = params["embed"].get("embed_scale")  # [V, 1] if quantized
        out_scale = None if out_scale is None else out_scale[:, 0]
    else:
        w_out = params["lm_head"]["weight"]
        out_scale = params["lm_head"].get("scale")
        out_scale = None if out_scale is None else out_scale.reshape(-1)
    with jax.named_scope("lm_head"):
        if w_out.dtype == jnp.int8:
            w_out = w_out.astype(h.dtype)
        logits = jax.lax.dot_general(
            h, w_out, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if out_scale is not None:
            logits = logits * out_scale[None, None, :]
        if cfg.logits_soft_cap:
            logits = cfg.logits_soft_cap * jnp.tanh(
                logits / cfg.logits_soft_cap
            )
    if return_moe_stats:
        return logits, kv, {"dropped": moe_dropped}
    return logits, kv


def prefill_attn_fn(q, k, v, layer_cache, positions, *, segment_ids=None,
                    backend=None, soft_cap=None):
    """Self-attention over the freshly computed K/V (no history)."""
    from helix_tpu.ops.attention import attention

    return attention(
        q, k, v,
        causal=True,
        q_positions=positions,
        kv_positions=positions,
        q_segment_ids=segment_ids,
        kv_segment_ids=segment_ids,
        logits_soft_cap=soft_cap,
        backend=backend,
    )
