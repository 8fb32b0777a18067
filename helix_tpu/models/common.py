"""Model configuration shared across families.

One config dataclass covers the decoder families the reference serves via
vLLM compose profiles (Llama-3, Phi-3, Qwen-2/3 — see
``design/sample-profiles/`` and BASELINE.md configs); family-specific
behaviour is expressed as data (activation, norm offsets, qk-norm, soft
caps), not subclasses, so one compiled forward function serves them all.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float = 500000.0
    # stored as a sorted tuple of (key, value) pairs so the config stays
    # hashable (it keys compiled-function caches); None = no scaling
    rope_scaling: Optional[tuple] = None
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"            # silu | gelu | gelu_tanh
    attention_bias: bool = False        # qkv bias (Qwen2)
    mlp_bias: bool = False
    qk_norm: bool = False               # per-head RMSNorm on q/k (Qwen3)
    logits_soft_cap: Optional[float] = None
    attn_logits_soft_cap: Optional[float] = None
    norm_offset: float = 0.0            # 1.0 for Gemma-style (1+w) RMSNorm
    max_position_embeddings: int = 8192
    dtype: str = "bfloat16"
    # multimodal rope sections (t, h, w) — set => Qwen2-VL-family text tower
    mrope_sections: Optional[tuple] = None
    # --- mixture of experts (Mixtral family); 0 = dense FFN ---
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # per-expert token capacity = factor * tokens * k / num_experts
    # (GShard-style dispatch; overflow tokens fall back to the residual)
    expert_capacity_factor: float = 1.5
    # 0 = dropless: tokens sorted by expert and one grouped product, at
    # every shape (DeepSeek).  > 0 keeps the GShard capacity dispatch.
    # --- DeepSeek-V2 family: fine-grained MoE with shared experts ---
    moe_intermediate_size: int = 0      # routed expert width (0: the FFN's)
    num_shared_experts: int = 0         # one shared MLP of this many widths
    first_k_dense: int = 0              # leading layers with a dense FFN
    # True: softmax over the top-k logits (Mixtral).  False: softmax over
    # all experts, the top-k probabilities kept as they are (DeepSeek's
    # norm_topk_prob false), times routed_scaling_factor.
    moe_renormalize: bool = True
    routed_scaling_factor: float = 1.0
    # --- multi-head latent attention (MLA); 0 = plain multi-head ---
    kv_lora_rank: int = 0               # compressed KV width, cached
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0           # shared rope key width, cached
    v_head_dim: int = 0
    # --- non-architectural serving metadata ---
    name: str = "unnamed"

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense if self.num_experts else 0

    def kv_token_shapes(self) -> tuple:
        """Per-token shapes of the two cached arrays, as the model hands
        them to ``attn_fn``: K and V ``(kv_heads, head_dim)`` each, or for
        latent attention the compressed latent ``(kv_lora_rank,)`` and
        the shared rope key ``(qk_rope_head_dim,)``: no head axis, no V."""
        if self.is_mla:
            return (self.kv_lora_rank,), (self.qk_rope_head_dim,)
        kv = (self.num_kv_heads, self.head_dim)
        return kv, kv

    @classmethod
    def from_hf_config(cls, hf: dict, name: str = "unnamed") -> "ModelConfig":
        """Build from a HuggingFace ``config.json`` dict (Llama/Qwen/Phi/
        Mistral-style decoder configs)."""
        hidden = hf["hidden_size"]
        heads = hf["num_attention_heads"]
        model_type = hf.get("model_type", "llama")
        rs = hf.get("rope_scaling") or {}
        mrope = (
            tuple(rs["mrope_section"]) if "mrope_section" in rs else None
        )
        # mrope is not a frequency scaling; store real scalings as a sorted
        # tuple so the config stays hashable
        rope_scaling = None
        if rs and mrope is None:
            rope_scaling = tuple(sorted(rs.items()))
        family = {"num_experts": hf.get("num_local_experts", 0)}
        if model_type == "deepseek_v2":
            if hf.get("q_lora_rank"):
                raise ValueError(
                    "deepseek_v2 with a compressed query (q_lora_rank "
                    f"{hf['q_lora_rank']}) is not supported: only the "
                    "direct query projection of DeepSeek-V2-Lite is"
                )
            if (hf.get("topk_method", "greedy") != "greedy"
                    or hf.get("scoring_func", "softmax") != "softmax"
                    or hf.get("moe_layer_freq", 1) != 1):
                raise ValueError(
                    "deepseek_v2: only the greedy softmax router with an "
                    "expert layer at every layer after the dense ones is "
                    "supported"
                )
            family = dict(
                num_experts=hf["n_routed_experts"],
                moe_intermediate_size=hf["moe_intermediate_size"],
                num_shared_experts=hf.get("n_shared_experts") or 0,
                first_k_dense=hf.get("first_k_dense_replace", 0),
                moe_renormalize=bool(hf.get("norm_topk_prob", False)),
                routed_scaling_factor=float(
                    hf.get("routed_scaling_factor", 1.0)),
                expert_capacity_factor=0.0,
                kv_lora_rank=hf["kv_lora_rank"],
                qk_nope_head_dim=hf["qk_nope_head_dim"],
                qk_rope_head_dim=hf["qk_rope_head_dim"],
                v_head_dim=hf["v_head_dim"],
            )
        return cls(
            **family,
            mrope_sections=mrope,
            vocab_size=hf["vocab_size"],
            hidden_size=hidden,
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=hf.get("num_key_value_heads", heads),
            head_dim=(
                hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
                if "kv_lora_rank" in family
                else hf.get("head_dim") or hidden // heads),
            intermediate_size=hf["intermediate_size"],
            rope_theta=hf.get("rope_theta", 10000.0),
            rope_scaling=rope_scaling,
            rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            hidden_act=hf.get("hidden_act", "silu"),
            attention_bias=hf.get("attention_bias", False)
            or model_type == "qwen2",
            mlp_bias=hf.get("mlp_bias", False),
            qk_norm=model_type == "qwen3",
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            num_experts_per_tok=hf.get("num_experts_per_tok", 2),
            name=name,
        )

    @classmethod
    def tiny(cls, **overrides) -> "ModelConfig":
        """A toy config for tests (fast to init/compile on one CPU core)."""
        base = dict(
            vocab_size=256,
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            intermediate_size=128,
            rope_theta=10000.0,
            max_position_embeddings=512,
            name="tiny",
        )
        base.update(overrides)
        return cls(**base)


# Canonical catalogue entries for the BASELINE.md configs — architecture
# hyperparameters only (weights come from HF checkpoints via
# ``models/loader.py``).
LLAMA3_8B = ModelConfig(
    vocab_size=128256,
    hidden_size=4096,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    intermediate_size=14336,
    rope_theta=500000.0,
    rms_norm_eps=1e-5,
    max_position_embeddings=8192,
    name="meta-llama/Meta-Llama-3-8B-Instruct",
)

PHI3_MINI = ModelConfig(
    vocab_size=32064,
    hidden_size=3072,
    num_layers=32,
    num_heads=32,
    num_kv_heads=32,
    head_dim=96,
    intermediate_size=8192,
    rope_theta=10000.0,
    max_position_embeddings=4096,
    name="microsoft/Phi-3-mini-4k-instruct",
)

QWEN2_7B = ModelConfig(
    vocab_size=152064,
    hidden_size=3584,
    num_layers=28,
    num_heads=28,
    num_kv_heads=4,
    head_dim=128,
    intermediate_size=18944,
    rope_theta=1000000.0,
    attention_bias=True,
    max_position_embeddings=32768,
    name="Qwen/Qwen2-7B-Instruct",
)

MIXTRAL_8X7B = ModelConfig(
    vocab_size=32000,
    hidden_size=4096,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    intermediate_size=14336,
    rope_theta=1000000.0,
    max_position_embeddings=32768,
    num_experts=8,
    num_experts_per_tok=2,
    name="mistralai/Mixtral-8x7B-Instruct-v0.1",
)

# DeepSeek-V2-Lite (https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/
# blob/main/config.json): latent attention, one dense layer then 26 of
# 64 routed + 2 shared experts, YaRN rope.  Served on one device with a
# bf16/f32 latent cache; a tp/ep/sp mesh, an int8 cache, adapters and
# tiered residency are refused at engine start (models/llama.py docstring).
DEEPSEEK_V2_LITE = ModelConfig(
    vocab_size=102400,
    hidden_size=2048,
    num_layers=27,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,
    intermediate_size=10944,
    rope_theta=10000.0,
    rope_scaling=tuple(sorted({
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn",
    }.items())),
    rms_norm_eps=1e-6,
    max_position_embeddings=163840,
    num_experts=64,
    num_experts_per_tok=6,
    expert_capacity_factor=0.0,
    moe_intermediate_size=1408,
    num_shared_experts=2,
    first_k_dense=1,
    moe_renormalize=False,
    routed_scaling_factor=1.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    name="deepseek-ai/DeepSeek-V2-Lite",
)

CATALOG = {
    m.name: m
    for m in (LLAMA3_8B, PHI3_MINI, QWEN2_7B, MIXTRAL_8X7B,
              DEEPSEEK_V2_LITE)
}
