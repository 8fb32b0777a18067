"""Llama-family decoder as functional JAX over a parameter pytree.

Covers Llama-3, Phi-3 (MHA, fused-free), Qwen-2 (attention bias), Qwen-3
(qk-norm) via ``ModelConfig`` switches — the decoder families the reference
serves through vLLM containers (``design/sample-profiles/``), here as owned
TPU-first code:

- Layers are **stacked** (every weight has a leading layer dim) in RUNS of
  one kind (``ModelConfig.layer_runs``: a token mixer, attention, gated
  short convolution, power retention, the gated delta rule or Mamba-2,
  times an FFN, dense or routed experts, or NONE: a block that is its
  mixer alone, under ``ModelConfig.hybrid_pattern``), one stack in
  the parameter tree and one ``lax.scan`` a run: a dense decoder is one
  run, DeepSeek-V2 two (``dense_layers`` then ``layers``), LFM2 thirteen,
  of which those that repeat back to back are one GROUP (a stack a run of
  the period, one loop over the repetitions): ``run00``, four times
  (``run01``, ``run02``), twice (``run09``, ``run10``).
- A layer that keeps a fixed per-sequence state (a conv layer's tail, a
  retention or delta-rule layer's matrix, a sliding-window layer's ring of
  K/V, a Mamba-2 layer's state and conv tail) has its look-back injected as
  attention is, through ONE argument
  (``state_fn``): the engine builds it from the kind's record
  (``models/mixers.py::STATE_MIXERS``: which earlier tokens are a token's
  own sequence, and the state a sequence carries between calls); without
  one the record's ``oracle`` runs, every row a whole sequence.  The
  COMPUTE around it (projections, gates, norms, rope; a window layer may
  have its own count of query heads and its own rope) is this module's.
- Three routers, by ``ModelConfig`` (``models/moe.py::route``); an MLP
  (dense, expert, shared expert) gated or not (``mlp_gated``), the routed
  experts in a latent or not (``moe_latent_size``).
- Attention is injected (``attn_fn``) so the same forward serves training
  (flash attention), prefill (flash + segment masks) and decode (paged
  attention over the engine's KV cache) without re-tracing model code.
- All matmuls run in bf16 on the MXU with fp32 accumulation
  (``preferred_element_type``); norms/softmax in fp32.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import zlib
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from helix_tpu.models.common import ModelConfig
from helix_tpu.models.mixers import STATE_MIXERS
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
    if name == "relu2":
        from helix_tpu.ops.grouped_matmul import relu2

        return relu2
    raise ValueError(f"unknown activation {name}")


def _seeded_int8(key, shape, dtype, std, embed, shardings):
    """int8 codes + scales of a seeded normal tensor, never materialised
    in float: a stacked ``[L, ...]`` tensor is drawn and quantized one
    layer at a time inside one jit (Qwen2-7B's bf16 ``w_gate`` alone is
    3.8 GB, its f32 draw 7.6 GB, on a 16 GB chip)."""
    # out_shardings is keyed like the output dict ({weight, scale});
    # lax.map stacks each layer's [1, out] scale row into [L, 1, out],
    # the shape quantize_tensor gives the stacked tensor
    build = _seeded_int8_builder(tuple(shape), jnp.dtype(dtype), std, embed)
    if shardings is None:
        return build(key)
    return jax.jit(build, out_shardings=shardings)(key)


@functools.lru_cache(maxsize=None)
def _seeded_int8_builder(shape, dtype, std, embed):
    """One compiled program a (shape, draw): a model of many runs of layers
    draws tensors of the same few shapes again and again."""
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

    return jax.jit(build)


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

    # the tensors the dense and Mixtral families always had keep the keys
    # they always had (a seed gives them the same weights as before); a
    # tensor that came later draws from its path
    kx = jax.random.split(jax.random.fold_in(key, 7), 4)
    legacy = {
        ("layers", "wq"): ks[1], ("layers", "wk"): ks[2],
        ("layers", "wv"): ks[3], ("layers", "wo"): ks[4],
        ("layers", "w_gate"): ks[5], ("layers", "w_up"): ks[6],
        ("layers", "w_down"): ks[7], ("layers", "router"): kx[0],
        ("layers", "experts", "w_gate"): kx[1],
        ("layers", "experts", "w_up"): kx[2],
        ("layers", "experts", "w_down"): kx[3],
    }

    def stack(n, moe, *at, mixer="attn", ffn=True):
        """One stack of ``n`` layers of one kind, at ``at`` in the tree
        (``ffn`` False: blocks of a mixer alone)."""

        def w(shape, *name, std=0.02):
            path = at + name
            k = legacy.get(path)
            if k is None:
                k = jax.random.fold_in(
                    jax.random.fold_in(key, 1000),
                    zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF)
            return weight(k, (n,) + shape, *path, std=std)

        def drawn(tag, name):
            """The key of a tensor that is drawn here, not by ``weight``."""
            return jax.random.fold_in(key, tag + zlib.crc32(
                "/".join(at + (name,)).encode()) % 1000)

        # a norm's gain is stored as an offset from ``cfg.norm_offset``
        unit = 1.0 - cfg.norm_offset
        norm = lambda width: {"weight": jnp.full((n, width), unit, dtype)}
        lp = {"attn_norm": norm(E)}
        if ffn:
            lp["mlp_norm"] = norm(E)
        if cfg.post_norms:
            lp["attn_post_norm"] = norm(E)
            lp["mlp_post_norm"] = norm(E)
        if mixer == "deltanet":
            nv, dv = cfg.linear_value_heads, cfg.linear_value_dim
            C = cfg.deltanet_channels
            lp["in_qkv"] = w((E, C), "in_qkv")
            lp["in_z"] = w((E, nv * dv), "in_z")
            lp["in_a"] = w((E, nv), "in_a")
            lp["in_b"] = w((E, nv), "in_b")
            # taps at 0.5, as the gated short convolution's: the branch is
            # of the size of its input
            lp["conv"] = {"taps": (jax.random.normal(
                drawn(2000, "conv"), (n, C, cfg.conv_kernel), jnp.float32)
                * 0.5).astype(dtype)}
            # ``g = -exp(A_log) * softplus(a + dt_bias)``: the rate ``A``
            # uniform in [0.5, 4] and the step uniform in log over [0.001,
            # 0.1] (its bias the inverse softplus): decays of 0.7 to 0.9995
            # a token, heads that forget in three tokens beside heads that
            # remember two thousand.  At the modeling file's ones and
            # uniform(0, 16) every head forgets in one, and no parity check
            # would see a state carried wrongly
            ka, kd = jax.random.split(drawn(5000, "A_log"))
            lp["A_log"] = {"bias": jnp.log(jax.random.uniform(
                ka, (n, nv), jnp.float32, 0.5, 4.0))}
            dt = jnp.exp(jax.random.uniform(
                kd, (n, nv), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
            lp["dt_bias"] = {"bias": dt + jnp.log(-jnp.expm1(-dt))}
            # under the silu gate the norm's gain is stored as it is
            lp["o_norm"] = ({"weight": jnp.ones((n, dv), dtype)}
                            if cfg.linear_gate == "silu" else norm(dv))
            lp["out_proj"] = w((nv * dv, E), "out_proj")
        elif mixer == "mamba2":
            Hm, C = cfg.mamba_heads, cfg.mamba_channels
            # ``[z | xBC | dt] = u W_in`` as three weights (the same bytes):
            # the step's logit is a product of its own, in float32
            lp["in_z"] = w((E, cfg.mamba_inner), "in_z")
            lp["in_xbc"] = w((E, C), "in_xbc")
            lp["in_dt"] = w((E, Hm), "in_dt")
            # taps at 0.5 (the branch is of the size of its input), a bias
            # at 0.1
            kt, kb = jax.random.split(drawn(2000, "conv"))
            lp["conv"] = {
                "taps": (jax.random.normal(
                    kt, (n, C, cfg.conv_kernel), jnp.float32)
                    * 0.5).astype(dtype),
                "bias": (jax.random.normal(kb, (n, C), jnp.float32)
                         * 0.1).astype(dtype)}
            # ``a = exp(-exp(A_log) * softplus(dt + dt_bias))``: the rate
            # uniform in [0.5, 4] and the step uniform in log over [0.001,
            # 0.1] (its bias the inverse softplus), as the delta rule's
            # above and for its reason: decays of 0.7 to 0.9995 a token.
            # The skip ``D`` uniform in [0.5, 1.5] (the initialiser's 1
            # would hide a ``D`` read from the wrong head)
            ka, kd, ks = jax.random.split(drawn(5000, "A_log"), 3)
            lp["A_log"] = {"bias": jnp.log(jax.random.uniform(
                ka, (n, Hm), jnp.float32, 0.5, 4.0))}
            dt = jnp.exp(jax.random.uniform(
                kd, (n, Hm), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
            lp["dt_bias"] = {"bias": dt + jnp.log(-jnp.expm1(-dt))}
            lp["D"] = {"bias": jax.random.uniform(
                ks, (n, Hm), jnp.float32, 0.5, 1.5)}
            lp["o_norm"] = norm(cfg.mamba_inner)
            lp["out_proj"] = w((cfg.mamba_inner, E), "out_proj")
        elif mixer == "conv":
            # a three-tap depthwise filter at std 0.02 passes a signal
            # thirty times smaller than the residual stream: the taps are
            # drawn at 0.5, so a conv layer's branch is of the size of an
            # attention layer's and a conv layer left out is seen
            lp["in_proj"] = w((E, 3 * E), "in_proj")
            lp["conv"] = {"taps": (jax.random.normal(
                jax.random.fold_in(key, 2000 + zlib.crc32(
                    "/".join(at).encode()) % 1000),
                (n, E, cfg.conv_kernel), jnp.float32) * 0.5).astype(dtype)}
            lp["out_proj"] = w((E, E), "out_proj")
        elif cfg.is_mla:
            R, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
            if cfg.q_lora_rank:
                lp["wq_a"] = w((E, cfg.q_lora_rank), "wq_a")
                lp["q_a_norm"] = norm(cfg.q_lora_rank)
                lp["wq_b"] = w((cfg.q_lora_rank, H * (dn + dr)), "wq_b")
            else:
                lp["wq"] = w((E, H * (dn + dr)), "wq")
            if cfg.attn_gate:
                lp["attn_gate"] = w((E, H * dv), "attn_gate")
            lp["wkv_a"] = w((E, R + dr), "wkv_a")
            lp["kv_norm"] = norm(R)
            lp["wkv_b"] = w((R, H * (dn + dv)), "wkv_b")
            lp["wo"] = w((H * dv, E), "wo")
            if cfg.is_dsa:
                # the indexer: queries from the compressed query, ONE key a
                # token and a weight a head from the layer's input.  The
                # key's LayerNorm is drawn (gain in [0.5, 1.5], bias at
                # 0.1): at the initialiser's 1 and 0 a gain or a bias left
                # out would not be seen
                Hi, Di = cfg.index_heads, cfg.index_head_dim
                lp["wq_idx"] = w((cfg.q_lora_rank or E, Hi * Di), "wq_idx")
                lp["wk_idx"] = w((E, Di), "wk_idx")
                lp["w_idx"] = w((E, Hi), "w_idx")
                kg, kb = jax.random.split(drawn(6000, "k_idx_norm"))
                lp["k_idx_norm"] = {
                    "weight": jax.random.uniform(
                        kg, (n, Di), jnp.float32, 0.5, 1.5).astype(dtype),
                    "bias": (jax.random.normal(kb, (n, Di), jnp.float32)
                             * 0.1).astype(dtype)}
        else:
            # a window layer may have its own count of query heads
            Ht = cfg.heads_of(mixer)
            lp["wq"] = w((E, Ht * D), "wq")
            lp["wk"] = w((E, KVH * D), "wk")
            lp["wv"] = w((E, KVH * D), "wv")
            lp["wo"] = w((Ht * D, E), "wo")
            if cfg.attn_gate and mixer in ("attn", "window"):
                # ONE value a head (or a head and channel): logits of std
                # ~0.9 from a normed input, gates of 0.3-0.7, so a gate left
                # out is seen
                lp["attn_gate"] = w(
                    (E, Ht * D if cfg.attn_gate_channels else Ht),
                    "attn_gate")
        if mixer == "retention":
            # a gate a kv head, ``sigmoid(W_g n + b_g)``.  The bias is drawn
            # uniform in [3, 7]: gates of 0.95-0.999, a state that remembers
            # hundreds to thousands of tokens.  At a bias of 0 it forgets in
            # two, and no parity check would see a state carried wrongly
            lp["g_proj"] = w((E, KVH), "g_proj")
            lp["g_bias"] = {"bias": jax.random.uniform(
                jax.random.fold_in(key, 4000 + zlib.crc32(
                    "/".join(at).encode()) % 1000),
                (n, KVH), jnp.float32, 3.0, 7.0)}
        # an MLP's weights: gate, up, down, or up and down alone
        mlp_names = ("w_gate", "w_up", "w_down")[0 if cfg.mlp_gated else 1:]

        def mlp(lead, d_in, width, *name):
            return {nm: w(lead + ((width, d_in) if nm == "w_down"
                                  else (d_in, width)), *name, nm)
                    for nm in mlp_names}

        if ffn and moe:
            # router + expert-stacked SwiGLU replaces the dense FFN
            # (models/moe.py); Mixtral's experts are as wide as the FFN
            X, Fx = cfg.num_experts, cfg.expert_width
            lp["router"] = w((E, X), "router")
            # the router scores every expert; the weights here are those
            # of the experts this chip holds
            X_all, X = X, cfg.num_held_experts
            if cfg.moe_expert_bias:
                # nonzero: at 0.03 it changes the top-4 of about half the
                # tokens and leaves every expert in play (at 0.1 it kept 3
                # of 32 out and the busiest took 4-6x the mean: PERF.md 6)
                lp["expert_bias"] = {"bias": jax.random.normal(
                    jax.random.fold_in(key, 3000 + zlib.crc32(
                        "/".join(at).encode()) % 1000),
                    (n, X_all), jnp.float32) * 0.03}
            # the experts' own width: the latent's, where they live in one
            Ex = cfg.moe_latent_size or E
            if cfg.moe_latent_size:
                lp["fc1"] = w((E, Ex), "fc1")
                lp["fc2"] = w((Ex, E), "fc2")
            lp["experts"] = mlp((X,), Ex, Fx, "experts")
            if cfg.num_shared_experts:
                lp["shared"] = mlp(
                    (), E, cfg.num_shared_experts * Fx, "shared")
                if cfg.shared_expert_gate:
                    # one logit a token, of std ~0.9 as the attention gate's
                    lp["shared_gate"] = w((E, 1), "shared_gate")
        elif ffn:
            lp.update(mlp((), E, F))
        if cfg.attention_bias and mixer in ("attn", "window"):
            for nm, width in (("wq", cfg.heads_of(mixer) * D),
                              ("wk", KVH * D), ("wv", KVH * D)):
                lp[nm]["bias"] = jnp.zeros((n, width), dtype)
        if cfg.qk_norm and mixer in ("attn", "retention", "window"):
            lp["q_norm"] = norm(D)
            lp["k_norm"] = norm(D)
        return lp

    # A dropless expert model's embedding rows are drawn at unit RMS.  At
    # 0.02 a token's own vector is lost under what the first random layer
    # adds to the residual stream, every token's hidden state is one
    # common vector, and every token picks the same six experts (on the
    # chip: 47-49 of 64 experts touched, the busiest at 8.5x the mean, the
    # count and with it the step's time following the seed).  No trained
    # router does that.  At 1.0 the decode rows of a step touch every
    # expert of every layer (PERF.md section 6, PR 28).  An untied head
    # keeps the logits' scale; under a tied one (interleaved mixers) the
    # final norm's gain is E ** -0.5: at 1 the logits' std is sqrt(E) and
    # sampling at temperature 1 is the argmax, one token for ever
    tied = cfg.tie_word_embeddings
    dropless_moe = (cfg.num_experts > 0 and cfg.expert_capacity_factor <= 0
                    and (not tied or cfg.layer_types is not None))
    params = {
        "embed": weight(ks[0], (V, E), "embed",
                        std=1.0 if dropless_moe else 0.02),
        "final_norm": {"weight": jnp.full(
            (E,), (E ** -0.5 if dropless_moe and tied else 1.0)
            - cfg.norm_offset, dtype)},
    }
    # a run of one kind is a stack of its own: two kinds of layer cannot
    # share one scan over stacked weights
    for group in cfg.layer_runs():
        for run in group.runs:
            params[run.key] = stack(run.count * group.reps, run.moe,
                                    run.key, mixer=run.mixer, ffn=run.ffn)
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
    def stack(moe, mixer="attn", ffn=True):
        lax_ = {"attn_norm": {"weight": ("layers", None)}}
        if ffn:
            lax_["mlp_norm"] = {"weight": ("layers", None)}
        if mixer in ("attn", "retention", "window"):
            lax_["wq"] = {"weight": ("layers", "embed", "heads")}
            lax_["wo"] = {"weight": ("layers", "heads", "embed")}
        if (cfg.attn_gate and not cfg.is_mla
                and mixer in ("attn", "window")):
            lax_["attn_gate"] = {"weight": ("layers", "embed", "heads")}
        if mixer == "retention":
            # (a mesh is refused for it: the state pool is one device's)
            lax_["g_proj"] = {"weight": ("layers", "embed", None)}
            lax_["g_bias"] = {"bias": ("layers", None)}
        if cfg.post_norms:
            lax_["attn_post_norm"] = {"weight": ("layers", None)}
            lax_["mlp_post_norm"] = {"weight": ("layers", None)}
        if mixer == "deltanet":
            # (a mesh is refused for it: the state pool is one device's)
            for nm in ("in_qkv", "in_z", "in_a", "in_b"):
                lax_[nm] = {"weight": ("layers", "embed", None)}
            lax_["conv"] = {"taps": ("layers", None, None)}
            lax_["A_log"] = {"bias": ("layers", None)}
            lax_["dt_bias"] = {"bias": ("layers", None)}
            lax_["o_norm"] = {"weight": ("layers", None)}
            lax_["out_proj"] = {"weight": ("layers", None, "embed")}
        elif mixer == "mamba2":
            # (a mesh is refused for it: the state pool is one device's)
            for nm in ("in_z", "in_xbc", "in_dt"):
                lax_[nm] = {"weight": ("layers", "embed", None)}
            lax_["conv"] = {"taps": ("layers", None, None),
                            "bias": ("layers", None)}
            for nm in ("A_log", "dt_bias", "D"):
                lax_[nm] = {"bias": ("layers", None)}
            lax_["o_norm"] = {"weight": ("layers", None)}
            lax_["out_proj"] = {"weight": ("layers", None, "embed")}
        elif mixer == "conv":
            # the gated convolution is depthwise over the hidden axis: its
            # projections are replicated (a mesh is refused for it)
            lax_["in_proj"] = {"weight": ("layers", "embed", None)}
            lax_["conv"] = {"taps": ("layers", None, None)}
            lax_["out_proj"] = {"weight": ("layers", None, "embed")}
        elif cfg.is_mla:
            if cfg.q_lora_rank:
                del lax_["wq"]
                lax_["wq_a"] = {"weight": ("layers", "embed", None)}
                lax_["q_a_norm"] = {"weight": ("layers", None)}
                lax_["wq_b"] = {"weight": ("layers", None, "heads")}
            if cfg.attn_gate:
                lax_["attn_gate"] = {"weight": ("layers", "embed", "heads")}
            # the latent projection is shared by every head: replicated
            lax_["wkv_a"] = {"weight": ("layers", "embed", None)}
            lax_["kv_norm"] = {"weight": ("layers", None)}
            lax_["wkv_b"] = {"weight": ("layers", None, "heads")}
            if cfg.is_dsa:
                # (a mesh is refused for it: the index pool is one device's)
                lax_["wq_idx"] = {"weight": ("layers", None, None)}
                lax_["wk_idx"] = {"weight": ("layers", "embed", None)}
                lax_["w_idx"] = {"weight": ("layers", "embed", None)}
                lax_["k_idx_norm"] = {"weight": ("layers", None),
                                      "bias": ("layers", None)}
        else:
            lax_["wk"] = {"weight": ("layers", "embed", "kv_heads")}
            lax_["wv"] = {"weight": ("layers", "embed", "kv_heads")}
        mlp = {
            "w_gate": {"weight": ("layers", "embed", "mlp")},
            "w_up": {"weight": ("layers", "embed", "mlp")},
            "w_down": {"weight": ("layers", "mlp", "embed")},
        }
        experts = {
            "w_gate": {"weight": ("layers", "expert", "embed", "mlp")},
            "w_up": {"weight": ("layers", "expert", "embed", "mlp")},
            "w_down": {"weight": ("layers", "expert", "mlp", "embed")},
        }
        if not cfg.mlp_gated:
            del mlp["w_gate"], experts["w_gate"]
        if ffn and moe:
            lax_["router"] = {"weight": ("layers", "embed", None)}
            if cfg.moe_expert_bias:
                lax_["expert_bias"] = {"bias": ("layers", None)}
            if cfg.moe_latent_size:
                lax_["fc1"] = {"weight": ("layers", "embed", None)}
                lax_["fc2"] = {"weight": ("layers", None, "embed")}
            lax_["experts"] = experts
            if cfg.num_shared_experts:
                lax_["shared"] = mlp
                if cfg.shared_expert_gate:
                    lax_["shared_gate"] = {"weight": ("layers", "embed", None)}
        elif ffn:
            lax_.update(mlp)
        if cfg.attention_bias and mixer in ("attn", "window"):
            lax_["wq"]["bias"] = ("layers", "heads")
            lax_["wk"]["bias"] = ("layers", "kv_heads")
            lax_["wv"]["bias"] = ("layers", "kv_heads")
        if cfg.qk_norm and mixer in ("attn", "retention", "window"):
            lax_["q_norm"] = {"weight": ("layers", None)}
            lax_["k_norm"] = {"weight": ("layers", None)}
        return lax_

    axes = {
        "embed": {"weight": ("vocab", "embed")},
        "final_norm": {"weight": (None,)},
    }
    for group in cfg.layer_runs():
        for run in group.runs:
            axes[run.key] = stack(run.moe, run.mixer, run.ffn)
    if not cfg.tie_word_embeddings:
        axes["lm_head"] = {"weight": ("embed", "vocab")}
    return axes


def _swiglu(x, p, act, adapter_ids=None, scoped=False, limit: float = 0.0):
    """``(act(x W_g) * (x W_u)) W_d`` over one dict of three weights
    (``limit``: the clamped form, ``ops.grouped_matmul.glu``); over a dict
    with no ``w_gate`` the ungated ``act(x W_u) W_d``."""
    from helix_tpu.ops.grouped_matmul import glu

    scope = jax.named_scope if scoped else (
        lambda _: contextlib.nullcontext())
    with scope("mlp.gate_up"):
        up = _dense(x, p["w_up"], adapter_ids)
        mid = act(up) if "w_gate" not in p else glu(
            _dense(x, p["w_gate"], adapter_ids), up, act, limit)
    with scope("mlp.down"):
        return _dense(mid.astype(x.dtype), p["w_down"], adapter_ids)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(qk_nope + qk_rope) ** -0.5`` times YaRN's ``mscale ** 2``."""
    from helix_tpu.ops.rope import yarn_attention_scales

    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * (
        yarn_attention_scales(cfg.rope_scaling)[1])


def mla_absorbed_weights(wkv_b: dict, cfg: ModelConfig, dtype):
    """``W_kvb [R, H * (dn + dv)]`` as the two absorbed factors
    ``W_UK [R, H, dn]`` and ``W_UV [R, H, dv]`` in ``dtype`` (an int8
    leaf is dequantised here: 2.1M values a layer)."""
    w = wkv_b["weight"]
    if "scale" in wkv_b:
        w = w.astype(jnp.float32) * wkv_b["scale"].astype(jnp.float32)
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    w = w.astype(dtype).reshape(cfg.kv_lora_rank, cfg.num_heads, dn + dv)
    return w[..., :dn], w[..., dn:]


def _mla_attention(h, p, layer_cache, cfg, positions, inv_freq, attn_fn,
                   post=None):
    """Multi-head latent attention (DeepSeek-V2) in the ABSORBED form, at
    every shape: each head's no-rope query is carried into the latent
    space (``q W_UK^T``), all heads attend ONE cached vector a token (the
    normed latent ``c`` and the shared rope key), and the attended latent
    leaves through ``W_UV``.  What ``attn_fn`` gets:

    - ``q [B, S, H, R + dr]``: absorbed query | rope query, already times
      the softmax scale (the op is called with scale 1);
    - ``k = c [B, S, R]``, ``v = k_pe [B, S, dr]``: the two cached arrays
      (no head axis, no V: the values are ``c`` itself);

    and returns the attended latent ``[B, S, H, R]``.  Rope pairs are
    rotated as published, ``(2i, 2i+1)``, and kept de-interleaved
    (``ops.rope.apply_rope_interleaved``).

    Behind an INDEXER (``cfg.is_dsa``, DeepSeek Sparse Attention): ``v`` is
    ``[k_pe | k_idx]``, the rope key and behind it the token's index key
    (both cached, each in its pool), and ``attn_fn`` gets a sixth argument
    ``qi [B, S, Hi * Di + Hi]``: the index queries and behind them the
    heads' weights times ``Hi ** -0.5 * Di ** -0.5`` (``index_queries``).
    Which keys a query then attends is ``attn_fn``'s (``ops/dsa.py``).

    A COMPRESSED query (``q_lora_rank``): ``q = n(x W_qa) W_qb``, two
    products and a norm where the direct form has one product.  A GATE
    (``attn_gate``): the heads' outputs times ``sigmoid(x W_g)``, a gate a
    head and value channel from the layer's input, before ``W_o``.
    ``post``: what the branch passes through before it joins the residual
    stream (a sandwich norm)."""
    from helix_tpu.ops.rope import apply_rope_interleaved, yarn_attention_scales

    B, S, E = h.shape
    H, R = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rot = yarn_attention_scales(cfg.rope_scaling)[0]
    w_uk, w_uv = mla_absorbed_weights(p["wkv_b"], cfg, h.dtype)
    x = rms_norm(h, p["attn_norm"]["weight"], cfg.rms_norm_eps,
                 cfg.norm_offset)
    q = None
    c_q = x
    if "wq_a" in p:
        with jax.named_scope("attn.q_a"):
            c_q = rms_norm(
                _dense(x, p["wq_a"]).astype(h.dtype),
                p["q_a_norm"]["weight"], cfg.rms_norm_eps, cfg.norm_offset)
        with jax.named_scope("attn.q_b"):
            q = _dense(c_q, p["wq_b"])
    with jax.named_scope("attn.q_proj"):
        q = _dense(x, p["wq"]) if q is None else q
        q = q.astype(h.dtype).reshape(B, S, H, dn + dr)
        q_pe = apply_rope_interleaved(q[..., dn:], positions, inv_freq, rot)
        q_abs = jnp.einsum(
            "bshd,rhd->bshr", q[..., :dn], w_uk,
            preferred_element_type=jnp.float32,
        )
        q_lat = (
            jnp.concatenate([q_abs, q_pe.astype(jnp.float32)], axis=-1)
            * mla_softmax_scale(cfg)
        ).astype(h.dtype)
    with jax.named_scope("attn.kv_latent"):
        ckv = _dense(x, p["wkv_a"]).astype(h.dtype)
        c = rms_norm(ckv[..., :R], p["kv_norm"]["weight"], cfg.rms_norm_eps,
                     cfg.norm_offset)
        k_pe = apply_rope_interleaved(ckv[..., R:], positions, inv_freq, rot)
    if cfg.is_dsa:
        qi, k_idx = _dsa_index(x, c_q, p, cfg, positions, inv_freq)
        k_pe = jnp.concatenate([k_pe, k_idx], axis=-1)
        with jax.named_scope("attn.sparse"):
            res = attn_fn(q_lat, c, k_pe, layer_cache, positions, qi)
    else:
        with jax.named_scope("attn.kernel"):
            res = attn_fn(q_lat, c, k_pe, layer_cache, positions)
    new_cache = None
    if isinstance(res, tuple):
        o_lat, new_cache = res
    else:
        o_lat = res
    with jax.named_scope("attn.out"):
        a = jnp.einsum(
            "bshr,rhd->bshd", o_lat, w_uv,
            preferred_element_type=jnp.float32,
        ).astype(h.dtype).reshape(B, S, H * dv)
    if "attn_gate" in p:
        with jax.named_scope("attn.gate"):
            a = (a.astype(jnp.float32) * jax.nn.sigmoid(
                _dense(x, p["attn_gate"]))).astype(h.dtype)
    with jax.named_scope("attn.out"):
        branch = _dense(a, p["wo"])
        h = h + (post(branch) if post else branch).astype(h.dtype)
    return h, (c, k_pe), new_cache


def _dsa_index(x, c_q, p, cfg, positions, inv_freq):
    """The indexer's side of a layer (DeepSeek Sparse Attention): from the
    normed input ``x`` and the compressed query ``c_q``,

    - ``q^I = c_q W_qb^I``, ``index_heads`` heads of ``index_head_dim``;
    - ``k^I = LayerNorm(x W_k^I)``, ONE key a token (gain and bias);
    - rope over the FIRST ``qk_rope_head_dim`` dims of each, with the
      attention's own frequencies and positions, the rest pass;
    - ``w = x W_w^I`` a head, in float32, times ``Hi ** -0.5 * Di ** -0.5``.

    Returns ``qi [B, S, Hi * Di + Hi]`` (queries | weights, the layer's
    dtype) and ``k_idx [B, S, Di]``.  A rotated part is left de-interleaved
    where the pairs are the published ``(2i, 2i+1)``: queries and keys share
    the permutation, so ``q . k`` is what it was."""
    from helix_tpu.ops.norms import layer_norm
    from helix_tpu.ops.quant import maybe_dequant_dense
    from helix_tpu.ops.rope import apply_rope_interleaved

    B, S, _ = x.shape
    Hi, Di, dr = cfg.index_heads, cfg.index_head_dim, cfg.qk_rope_head_dim

    def rope(t):
        return jnp.concatenate(
            [apply_rope_interleaved(t[..., :dr], positions, inv_freq),
             t[..., dr:]], axis=-1)

    with jax.named_scope("attn.index.q"):
        q_idx = rope(_dense(c_q, p["wq_idx"]).astype(x.dtype).reshape(
            B, S, Hi, Di))
        w_idx = maybe_dequant_dense(
            x, p["w_idx"], compute_dtype=jnp.float32) * (
                Hi ** -0.5 * Di ** -0.5)
        qi = jnp.concatenate(
            [q_idx.reshape(B, S, Hi * Di), w_idx.astype(x.dtype)], axis=-1)
    with jax.named_scope("attn.index.k"):
        k_idx = rope(layer_norm(
            _dense(x, p["wk_idx"]).astype(x.dtype),
            p["k_idx_norm"]["weight"], p["k_idx_norm"]["bias"], 1e-6))
    return qi, k_idx


def _conv_mixer(h, p, layer_cache, cfg, state_fn):
    """Gated short convolution (LFM2): ``(B, C, x) = in_proj(u)``,
    ``y = conv(B * x)`` depthwise and causal over the sequence, ``out_proj(C
    * y)``.  ``state_fn(z, taps, layer_cache) -> (y, new_cache)`` owns the
    look-back: which earlier tokens are a token's own sequence, and the
    state a sequence carries between calls."""
    with jax.named_scope("conv.in_proj"):
        x = rms_norm(h, p["attn_norm"]["weight"], cfg.rms_norm_eps,
                     cfg.norm_offset)
        b, c, x = jnp.split(_dense(x, p["in_proj"]).astype(h.dtype), 3,
                            axis=-1)
        z = b * x
    with jax.named_scope("conv.mix"):
        y, new_cache = state_fn(z, p["conv"]["taps"], layer_cache)
        y = (c.astype(jnp.float32) * y).astype(h.dtype)
    with jax.named_scope("conv.out_proj"):
        h = h + _dense(y, p["out_proj"]).astype(h.dtype)
    return h, new_cache


def _retention_mixer(h, p, layer_cache, cfg, positions, inv_freq,
                     state_fn):
    """Power retention (Brumby): GQA-shaped ``q, k, v`` with per-head norms
    and rope as in the Qwen3 block, a gate a kv head ``log g = logsigmoid(
    W_g n + b_g)``, and ``y_t = sum_s a_ts v_s / (sum_s a_ts + eps)`` with
    ``a_ts = prod_{r in (s, t]} g_r * (q_t . k_s / sqrt d) ** 2``.
    ``state_fn(q, k, v, log_g, layer_cache) -> (y, new_cache)`` owns the
    sum: which earlier tokens are a token's own sequence, and the matrix
    state a sequence carries between calls (``ops/retention.py``).  ``q``
    arrives in float32 times ``head_dim ** -0.5``."""
    B, S, E = h.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope("retention.qkvg"):
        x = rms_norm(h, p["attn_norm"]["weight"], cfg.rms_norm_eps,
                     cfg.norm_offset)
        q = _dense(x, p["wq"]).astype(h.dtype).reshape(B, S, H, D)
        k = _dense(x, p["wk"]).astype(h.dtype).reshape(B, S, KVH, D)
        v = _dense(x, p["wv"]).astype(h.dtype).reshape(B, S, KVH, D)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"]["weight"], cfg.rms_norm_eps,
                         cfg.norm_offset)
            k = rms_norm(k, p["k_norm"]["weight"], cfg.rms_norm_eps,
                         cfg.norm_offset)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        from helix_tpu.ops.quant import maybe_dequant_dense

        # the gate's logit stays float32: a context of thousands of tokens
        # multiplies thousands of gates
        log_g = jax.nn.log_sigmoid(
            maybe_dequant_dense(x, p["g_proj"], compute_dtype=jnp.float32)
            + p["g_bias"]["bias"].astype(jnp.float32))
    with jax.named_scope("retention.mix"):
        y, new_cache = state_fn(
            q.astype(jnp.float32) * D ** -0.5, k, v, log_g, layer_cache)
    with jax.named_scope("retention.out_proj"):
        h = h + _dense(
            y.astype(h.dtype).reshape(B, S, H * D), p["wo"]).astype(h.dtype)
    return h, new_cache


def _deltanet_mixer(h, p, layer_cache, cfg, state_fn, post=None):
    """The gated delta rule (``ops/deltanet.py``): ``q | k | v = silu(conv(x
    W_qkv))`` through a causal depthwise convolution, a write strength
    ``beta = sigmoid(x W_b)`` and a log decay ``g = -exp(A_log) * softplus(x
    W_a + dt_bias)`` a value head, the rule, then ``n_h(o) * (scale *
    sigmoid(x W_z))`` with ``n_h`` an RMSNorm over a head's channels (or,
    ``cfg.linear_gate`` "silu", ``n_h(o) * silu(x W_z)`` with ``n_h``'s gain
    PLAIN whatever ``cfg.norm_offset``), and ``W_o``.  ``state_fn(x W_qkv, g, beta, taps, layer_cache) -> (o [B, S,
    heads, dv] float32, new_cache)`` owns the look-back: the convolution's
    tail and the matrix state a sequence carries between calls."""
    from helix_tpu.ops.quant import maybe_dequant_dense

    B, S, E = h.shape
    nv, dv = cfg.linear_value_heads, cfg.linear_value_dim
    with jax.named_scope("deltanet.in_proj"):
        x = rms_norm(h, p["attn_norm"]["weight"], cfg.rms_norm_eps,
                     cfg.norm_offset)
        qkv = _dense(x, p["in_qkv"]).astype(h.dtype)
        z = _dense(x, p["in_z"])
        # the decay's logit stays float32: a context of thousands of tokens
        # multiplies thousands of decays
        f32 = dict(compute_dtype=jnp.float32)
        beta = jax.nn.sigmoid(maybe_dequant_dense(x, p["in_b"], **f32))
        g = -jnp.exp(p["A_log"]["bias"].astype(jnp.float32)) * (
            jax.nn.softplus(maybe_dequant_dense(x, p["in_a"], **f32)
                            + p["dt_bias"]["bias"].astype(jnp.float32)))
    o, new_cache = state_fn(
        qkv, g, beta, p["conv"]["taps"], layer_cache)
    with jax.named_scope("deltanet.out_proj"):
        if cfg.linear_gate == "silu":
            y = rms_norm(o, p["o_norm"]["weight"], cfg.linear_norm_eps)
            y = y * jax.nn.silu(
                z.astype(jnp.float32).reshape(B, S, nv, dv))
        else:
            y = rms_norm(o, p["o_norm"]["weight"], cfg.linear_norm_eps,
                         cfg.norm_offset)
            y = y * (cfg.linear_gate_scale * jax.nn.sigmoid(
                z.astype(jnp.float32).reshape(B, S, nv, dv)))
        branch = _dense(y.astype(h.dtype).reshape(B, S, nv * dv),
                        p["out_proj"])
        h = h + (post(branch) if post else branch).astype(h.dtype)
    return h, new_cache


def _mamba2_mixer(h, p, layer_cache, cfg, state_fn):
    """Mamba-2 (``ops/ssd.py``): ``[z | xBC | dt] = u W_in``, ``x | B | C =
    silu(conv(xBC) + b)`` through a causal depthwise convolution, a step ``dt
    = softplus(dt + dt_bias)`` and a log decay ``dt * A`` with ``A =
    -exp(A_log)`` a head, the state space with its skip ``D x``, then ``g *
    GroupRMS(y * silu(z))`` (the gate BEFORE the norm, the norm over each
    group's channels) and ``W_out``.  ``state_fn(xBC, dt, dt * A, taps, bias,
    D, layer_cache) -> (y [B, S, heads, head dim] float32, new_cache)`` owns
    the look-back: the convolution's tail and the state ``h`` a sequence
    carries between calls."""
    from helix_tpu.ops.quant import maybe_dequant_dense

    B, S, E = h.shape
    G = cfg.mamba_groups
    with jax.named_scope("ssd.in_proj"):
        u = rms_norm(h, p["attn_norm"]["weight"], cfg.rms_norm_eps,
                     cfg.norm_offset)
        z = _dense(u, p["in_z"])
        xbc = _dense(u, p["in_xbc"]).astype(h.dtype)
        # the step's logit stays float32: a context of thousands of tokens
        # multiplies thousands of decays
        dt = jax.nn.softplus(
            maybe_dequant_dense(u, p["in_dt"], compute_dtype=jnp.float32)
            + p["dt_bias"]["bias"].astype(jnp.float32))
        la = -jnp.exp(p["A_log"]["bias"].astype(jnp.float32)) * dt
    y, new_cache = state_fn(
        xbc, dt, la, p["conv"]["taps"], p["conv"]["bias"], p["D"]["bias"],
        layer_cache)
    with jax.named_scope("ssd.norm"):
        y = (y.reshape(B, S, -1) * jax.nn.silu(z.astype(jnp.float32))
             ).reshape(B, S, G, -1)
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        y = y.reshape(B, S, -1) * (
            cfg.norm_offset + p["o_norm"]["weight"].astype(jnp.float32))
    with jax.named_scope("ssd.out"):
        h = h + _dense(y.astype(h.dtype), p["out_proj"]).astype(h.dtype)
    return h, new_cache


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
    stacked_experts=None,
    moe_backend=None,
    state_fn=None,
    moe_decode_rows: int = 0,
    mixer: str = "attn",
    moe_probe=None,
):
    """One decoder block. h: [B, S, E].  ``mixer``: the layer's kind (a GQA
    layer's, ``"attn"`` or ``"window"``, decides its query heads, its rope
    through ``inv_freq`` and its look-back; the other mixers are told by
    their weights).  ``state_fn``: the look-back of a kind with a
    per-sequence state (``STATE_MIXERS``); None: its record's ``oracle``,
    every row a whole sequence.  ``moe_probe = (layer's index in the model,
    the pass's input ids)``: for ``models.moe.PROBE``, where one is set.

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
    if state_fn is None and mixer in STATE_MIXERS:
        state_fn = STATE_MIXERS[mixer].oracle(cfg, positions)

    def post_norm(name):
        """The sandwich norm on a branch's output, if the block has one."""
        if name not in p:
            return None
        return lambda y: rms_norm(
            y, p[name]["weight"], cfg.rms_norm_eps, cfg.norm_offset)

    # --- attention ---
    # the named scopes are what a profiler trace calls these operations,
    # whatever number the compiler gives their fusions
    if "in_proj" in p:
        # the layer's mixer, like its FFN, is what its weights are
        k = v = None
        h, new_cache = _conv_mixer(h, p, layer_cache, cfg, state_fn)
    elif "g_proj" in p:
        k = v = None
        h, new_cache = _retention_mixer(
            h, p, layer_cache, cfg, positions, inv_freq, state_fn)
    elif "in_qkv" in p:
        k = v = None
        h, new_cache = _deltanet_mixer(
            h, p, layer_cache, cfg, state_fn, post_norm("attn_post_norm"))
    elif "in_xbc" in p:
        k = v = None
        h, new_cache = _mamba2_mixer(h, p, layer_cache, cfg, state_fn)
    elif cfg.is_mla:
        h, (k, v), new_cache = _mla_attention(
            h, p, layer_cache, cfg, positions, inv_freq, attn_fn,
            post_norm("attn_post_norm"))
    else:
        # the scopes are the kind's: ``attn.*`` on a full layer, ``window.*``
        # on a sliding one
        sc = "window" if mixer == "window" else "attn"
        H = cfg.heads_of(mixer)
        # YaRN's stated ``attention_factor`` multiplies cos and sin: it acts
        # on the rotated dims only
        rot = float(dict(cfg.rope_of(mixer)[2] or ()).get(
            "attention_factor", 1.0))
        with jax.named_scope(f"{sc}.qkv"):
            x = rms_norm(
                h, p["attn_norm"]["weight"], cfg.rms_norm_eps,
                cfg.norm_offset,
            )
            q = _dense(x, p["wq"], adapter_ids).reshape(B, S, H, D)
            k = _dense(x, p["wk"], adapter_ids).reshape(B, S, KVH, D)
            v = _dense(x, p["wv"], adapter_ids).reshape(B, S, KVH, D)
            if cfg.qk_norm:
                # a family whose norms are zero-centred stores these gains
                # as offsets from 1 too
                q = rms_norm(q, p["q_norm"]["weight"], cfg.rms_norm_eps,
                             cfg.norm_offset)
                k = rms_norm(k, p["k_norm"]["weight"], cfg.rms_norm_eps,
                             cfg.norm_offset)
            if cfg.attn_rope:
                q = apply_rope(q, positions, inv_freq, rot)
                k = apply_rope(k, positions, inv_freq, rot)
        with jax.named_scope(f"{sc}.kernel"):
            if mixer == "window":
                res = state_fn(q, k, v, layer_cache)
            else:
                res = attn_fn(q, k, v, layer_cache, positions)
        new_cache = None
        if isinstance(res, tuple):
            attn_out, new_cache = res
        else:
            attn_out = res
        if "attn_gate" in p:
            # one sigmoid gate a head (``attn_gate_channels``: a head and
            # channel), from the branch's normed input
            with jax.named_scope(f"{sc}.gate"):
                # (one expression, the gate's spread inside it: a gate a
                # head lowers to the text it lowered to before the channel
                # form came)
                spread = ((lambda g: g.reshape(B, S, H, D))
                          if cfg.attn_gate_channels
                          else (lambda g: g[..., None]))
                attn_out = (attn_out.astype(jnp.float32) * spread(
                    jax.nn.sigmoid(
                        _dense(x, p["attn_gate"]).astype(jnp.float32)
                    ))).astype(h.dtype)
        with jax.named_scope(f"{sc}.out"):
            h = h + _dense(
                attn_out.reshape(B, S, H * D), p["wo"], adapter_ids)
        if mixer == "window":
            # its K/V live in its slot's ring, not in the pass's pages
            k = v = None

    # --- mlp: the layer's kind is what its weights are ---
    from helix_tpu.models.moe import STATS, moe_ffn

    if "mlp_norm" not in p:
        # the block is its mixer alone
        return h, (k, v), new_cache, jnp.zeros((STATS,), jnp.float32)
    x = rms_norm(h, p["mlp_norm"]["weight"], cfg.rms_norm_eps, cfg.norm_offset)
    act = _act(cfg.hidden_act)
    moe_stats = None
    if "router" in p:
        router_w = p["router"]["weight"]
        if router_w.dtype == jnp.int8:
            # dequantise in fp32: the router's softmax runs in fp32, and
            # rounding through bf16 here could flip near-tied top-k picks
            router_w = router_w.astype(jnp.float32) * p["router"][
                "scale"
            ].astype(jnp.float32)
        xr = x
        if "fc1" in p:
            # the routed experts live in a latent; the router and the shared
            # expert read the un-projected input
            with jax.named_scope("moe.latent_in"):
                xr = _dense(x, p["fc1"]).astype(h.dtype)
        moe_out, moe_stats = moe_ffn(
            xr, router_w, p.get("experts"), cfg, act,
            router_x=x if "fc1" in p else None,
            token_mask=moe_token_mask,
            return_stats=True,
            stacked_experts=stacked_experts,
            backend=moe_backend,
            expert_bias=(p["expert_bias"]["bias"]
                         if "expert_bias" in p else None),
            decode_rows=moe_decode_rows,
            probe=None if moe_probe is None else (*moe_probe, positions),
        )
        if "fc2" in p:
            with jax.named_scope("moe.latent_out"):
                moe_out = _dense(moe_out, p["fc2"]).astype(h.dtype)
        if "shared" in p:
            with jax.named_scope("moe.shared"):
                shared = _swiglu(x, p["shared"], act, limit=cfg.swiglu_limit)
            if "shared_gate" in p:
                # ONE sigmoid gate a token on the shared expert, its logit
                # in float32
                from helix_tpu.ops.quant import maybe_dequant_dense

                with jax.named_scope("moe.shared_gate"):
                    shared = (shared.astype(jnp.float32) * jax.nn.sigmoid(
                        maybe_dequant_dense(
                            x, p["shared_gate"], compute_dtype=jnp.float32))
                              ).astype(h.dtype)
            moe_out = moe_out + shared
        ffn = moe_out
    else:
        ffn = _swiglu(x, p, act, adapter_ids, scoped=True,
                      limit=cfg.swiglu_limit)
    post = post_norm("mlp_post_norm")
    h = h + (post(ffn) if post else ffn).astype(h.dtype)
    if moe_stats is None:
        moe_stats = jnp.zeros((STATS,), jnp.float32)
    return h, (k, v), new_cache, moe_stats


def scan_decoder_blocks(
    h, layers_params, num_layers: int, block, layer_caches, carry_caches,
    first_layer: int = 0, with_index: bool = False,
):
    """Shared cache-protocol dispatch for decoder towers (llama families +
    the Qwen2-VL mrope tower share this so the two protocols cannot
    diverge), over ONE stack of layers of one kind.

    ``block(h, layer_params, layer_cache) -> (h, (k, v), new_cache,
    moe_stats)``.

    - xs mode (``layer_caches`` or no cache): the scan slices a per-layer
      cache view; returns (h, kv, moe_stats) with kv stacked [L, ...]
      for the caller's scatter.
    - carry mode (``carry_caches``): the full cache pytree threads through
      the scan carry and block's attn_fn receives ``(caches, layer_idx)``,
      the index counted from ``first_layer`` (a model of two stacks runs
      the pool's layer index through both); returns (h, final_caches,
      moe_stats).

    ``moe_stats`` is what each layer's block gave, stacked ``[L, ...]``
    (``models.moe.expert_load_stats`` rows; zeros for dense layers).
    ``with_index``: ``block`` also gets the layer's index IN THIS STACK as
    a fourth argument (for weights it reads whole, not as a scan slice).
    """
    idx = jnp.arange(num_layers, dtype=jnp.int32)

    def call(h, layer_params, cache, i):
        if with_index:
            return block(h, layer_params, cache, i)
        return block(h, layer_params, cache)

    if carry_caches is not None:
        def carry_body(carry, xs):
            h, caches = carry
            layer_params, i = xs
            h, _, caches, d = call(
                h, layer_params, (caches, first_layer + i), i)
            return (h, caches), d

        xs = (layers_params, idx)
        (h, kv), stats = jax.lax.scan(carry_body, (h, carry_caches), xs)
    else:
        def scan_body(h, xs):
            layer_params, layer_cache, i = xs
            h, kv, _, d = call(h, layer_params, layer_cache, i)
            return h, (kv, d)

        if layer_caches is None:
            # lax.scan needs every xs leaf to have a leading L dim; "no
            # history" is a zero-length dummy the attn_fn never touches.
            layer_caches = jnp.zeros((num_layers, 0), jnp.int32)
        h, (kv, stats) = jax.lax.scan(
            scan_body, h, (layers_params, layer_caches, idx)
        )
    return h, kv, stats


def layer_stacks(params: Params, cfg: ModelConfig) -> list:
    """``[(reps, [(stacked layer weights, run)])]`` in layer order: one
    entry a group of runs (``ModelConfig.layer_runs``), one stack a run."""
    return [(group.reps, [(params[run.key], run) for run in group.runs])
            for group in cfg.layer_runs()]


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
    return_moe_stats: bool = False,  # also return {"dropped": int32,
                          # "vector": f32[5]}: drops summed over layers,
                          # and the step's routing load
    adapter_ids=None,     # [B, S] i32: per-token multi-LoRA pool slot
                          # (0 = identity); None = no batched adapters
    moe_backend=None,     # the dropless experts' grouped product, as the
                          # attention dispatchers take it (models/moe.py)
    state_fn=None,        # the look-back of the model's layers with a
                          # per-sequence state, as its kind's mixer calls
                          # it (``models/mixers.py``); None: every row is
                          # a whole sequence
    moe_decode_rows: int = 0,  # the last n tokens of the axis are decode
                          # rows riding a prefill's pass: capacity dispatch
                          # leaves them dropless (``models/moe.py``)
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

    # one table a KIND of layer: a window layer may rotate another width at
    # another base than a full one
    inv_freqs = {
        m: jnp.asarray(rope_frequencies(*cfg.rope_of(m)))
        for m in ("attn",) + (("window",) if cfg.num_window_layers else ())
    }
    h = embed_lookup(params["embed"], tokens, jnp.dtype(cfg.dtype))

    from helix_tpu.models import moe

    def run_layers(h, carry, stack, run, rep, layer0=0):
        """The ``run.count`` layers of one run at repetition ``rep`` of its
        group (0 for a plain run; a traced index inside a group's loop):
        ``stack`` holds them alone, the first of them layer ``layer0`` of
        the model.  Returns ``(h, kv or carry, stats)``."""
        whole = None
        if "experts" in params[run.key] and cfg.expert_capacity_factor <= 0:
            # the grouped product is a Pallas kernel (ops/grouped_matmul.py):
            # it reads its weights from a whole buffer, and a scan's
            # per-layer slice of the stacked experts would be copied out
            # for it (184 MB a projection a layer).  So the experts stay
            # out of the scan's slices and the kernel's block index picks
            # the layer (a scalar-prefetch operand; models/moe.py), out of
            # ALL the run's layers, every repetition's
            whole = params[run.key]["experts"]
            stack = {k: v for k, v in stack.items() if k != "experts"}

        def block(h, layer_params, layer_cache, i):
            return _layer(
                h, layer_params, layer_cache, cfg, positions,
                inv_freqs["window" if run.mixer == "window" else "attn"],
                attn_fn, moe_token_mask=moe_token_mask,
                adapter_ids=adapter_ids,
                stacked_experts=None if whole is None else (
                    whole, rep * run.count + i),
                moe_backend=moe_backend, state_fn=state_fn,
                moe_decode_rows=moe_decode_rows, mixer=run.mixer,
                moe_probe=(None if moe.PROBE is None
                           else (layer0 + i, tokens)),
            )

        # the cache's layer index counts the layers of the run's mixer:
        # an attention run's is the page pool's, a conv run's the state
        # pool's
        first = run.first + rep * run.step
        return scan_decoder_blocks(
            h, stack, run.count, block,
            None if layer_caches is None or run.mixer != "attn"
            else jax.tree.map(
                lambda c: c[run.first:run.first + run.count], layer_caches),
            carry, first_layer=first, with_index=True,
        )

    kvs, stats = [], []
    layer0 = 0              # layers before the group at hand
    for reps, runs in layer_stacks(params, cfg):
        span = sum(run.count for _, run in runs)   # layers a repetition
        if reps == 1:
            for stack, run in runs:
                h, kv, st = run_layers(h, carry_caches, stack, run, 0,
                                       layer0)
                layer0 += run.count
                if carry_caches is not None:
                    carry_caches = kv
                if carry_caches is not None or run.mixer == "attn":
                    kvs.append(kv)
                stats.append(st)
            continue
        if layer_caches is not None:
            raise NotImplementedError(
                "per-layer cache views (layer_caches) over a repeated "
                "group of runs: use carry_caches")
        # a period of the layer pattern, ``reps`` times: ONE loop whose body
        # holds one inner loop a run, over the run's stack viewed
        # [reps, count, ...] (the experts stay whole, see above)
        xs = [jax.tree.map(
                  lambda a, n=run.count: a.reshape((reps, n) + a.shape[1:]),
                  {k: v for k, v in stack.items() if k != "experts"})
              for stack, run in runs]

        def period(carry, x):
            h, caches = carry
            rep, stacks = x
            outs = []
            at = layer0 + rep * span
            for part, (_, run) in zip(stacks, runs):
                h, kv, st = run_layers(h, caches, part, run, rep, at)
                at = at + run.count
                if caches is not None:
                    caches = kv
                outs.append((None if caches is not None else kv, st))
            return (h, caches), outs

        (h, carry_caches), outs = jax.lax.scan(
            period, (h, carry_caches),
            (jnp.arange(reps, dtype=jnp.int32), xs))
        layer0 += reps * span
        if carry_caches is not None:
            kvs.append(carry_caches)
        # layer order within the group: repetition-major
        merged = [jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]), o) for o in outs]
        for (kv, st), (_, run) in zip(merged, runs):
            if carry_caches is None and run.mixer == "attn":
                kvs.append(kv)
            stats.append(st)
    if not kvs:
        kv = None          # no layer with pages and no cache: nothing fresh
    elif carry_caches is not None or len(kvs) == 1:
        kv = kvs[-1]
    else:
        kv = jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *kvs)
    stats = jnp.concatenate(stats, axis=0)                   # [L, 4]
    h = rms_norm(h, params["final_norm"]["weight"], cfg.rms_norm_eps, cfg.norm_offset)
    out = h if return_hidden else lm_head(params, cfg, h)
    if return_moe_stats:
        return out, kv, {
            "dropped": jnp.sum(stats[:, 0]).astype(jnp.int32),
            # [dropped, routed, busiest expert over the mean (max over
            # layers), distinct experts touched and the grouped product's
            # tile fill (means over MoE layers), assignments to experts
            # held elsewhere]
            "vector": jnp.stack([
                jnp.sum(stats[:, 0]), jnp.sum(stats[:, 1]),
                jnp.max(stats[:, 2]),
                jnp.sum(stats[:, 3]) / max(cfg.num_moe_layers, 1),
                jnp.sum(stats[:, 4]) / max(cfg.num_moe_layers, 1),
                jnp.sum(stats[:, 5]),
            ]),
        }
    return out, kv


def lm_head(params: Params, cfg: ModelConfig, h):
    """Logits ``[..., V]`` (float32) of normed hidden rows ``h [..., E]``:
    what ``forward`` ends with; a caller that took ``return_hidden`` calls
    it on the rows it samples only."""
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
            h, w_out, (((h.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if out_scale is not None:
            logits = logits * out_scale
        if cfg.logits_soft_cap:
            logits = cfg.logits_soft_cap * jnp.tanh(
                logits / cfg.logits_soft_cap
            )
    return logits


def prefill_attn_fn(q, k, v, layer_cache, positions, qi=None, *,
                    segment_ids=None, backend=None, soft_cap=None,
                    cfg=None):
    """Self-attention over the freshly computed K/V (no history).  For a
    latent-attention model ``k``/``v`` are the latent and the rope key
    (``_mla_attention``) and the plain absorbed-form reference runs."""
    from helix_tpu.ops.attention import attention

    if qi is not None:
        # behind an indexer (``cfg`` says how many heads and keys): every
        # query attends the keys its index scores choose
        from helix_tpu.ops.dsa import dsa_dense_attention

        seg = (jnp.ones_like(positions) if segment_ids is None
               else segment_ids)
        return jax.vmap(
            lambda q1, c1, r1, i1, p1, s1: dsa_dense_attention(
                q1, c1, r1, i1, positions=p1, segment_ids=s1,
                index_heads=cfg.index_heads, topk=cfg.index_topk)
        )(q, k, v, qi, positions, seg)
    if k.ndim == 3:
        from helix_tpu.ops.paged import mla_attention_reference

        seg = (jnp.ones_like(positions) if segment_ids is None
               else segment_ids)
        return jax.vmap(
            lambda q1, c1, r1, p1, s1: mla_attention_reference(
                q1, c1, r1, q_positions=p1, kv_positions=p1,
                q_segment_ids=s1, kv_segment_ids=s1)
        )(q, k, v, positions, seg)

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
