"""HF safetensors checkpoint -> stacked-layer JAX parameter tree.

The reference's weight path is "vLLM downloads from HF inside the container"
(progress surfaced by ``api/pkg/composemgr/hfprogress.go``).  Here loading is
owned: safetensors are memory-mapped on the host, transposed into our
[in, out] matmul convention, stacked along a leading layer axis for the
scan-based forward, then device_put with the model's NamedShardings so each
chip only materialises its shard (no full-model HBM spike on load).

Supports Llama/Qwen2/Qwen3 per-projection layouts, Phi-3's fused
``qkv_proj``/``gate_up_proj``, DeepSeek-V2's latent attention and
Qwen3-Next's delta-rule / gated-attention / expert layers
(``_qwen3_next_tree``: the published file's interleaved projections
un-interleaved at load).
"""

from __future__ import annotations

import json
import os
from typing import Optional

import ml_dtypes  # noqa: F401  — registers bfloat16 with numpy
import numpy as np

from helix_tpu.models.common import ModelConfig


def _open_shards(model_dir: str):
    """Yield (tensor_name -> numpy array) access across all safetensors files."""
    from safetensors import safe_open

    index_path = os.path.join(model_dir, "model.safetensors.index.json")
    handles = {}
    name_to_file = {}
    if os.path.exists(index_path):
        with open(index_path) as f:
            index = json.load(f)
        name_to_file = index["weight_map"]
        files = sorted(set(name_to_file.values()))
    else:
        files = [
            f for f in sorted(os.listdir(model_dir)) if f.endswith(".safetensors")
        ]
    for fname in files:
        handles[fname] = safe_open(
            os.path.join(model_dir, fname), framework="np"
        )
    if not name_to_file:
        for fname, h in handles.items():
            for name in h.keys():
                name_to_file[name] = fname

    class Shards:
        def __init__(self):
            self.names = set(name_to_file)

        def get(self, name: str) -> np.ndarray:
            return handles[name_to_file[name]].get_tensor(name)

        def __contains__(self, name):
            return name in self.names

    return Shards()


def load_config(model_dir: str, name: Optional[str] = None) -> ModelConfig:
    with open(os.path.join(model_dir, "config.json")) as f:
        hf = json.load(f)
    return ModelConfig.from_hf_config(hf, name=name or os.path.basename(model_dir))


def load_params(
    model_dir: str,
    cfg: Optional[ModelConfig] = None,
    *,
    mesh=None,
    logical_axes=None,
    dtype=None,
    quantize: bool = False,
):
    """Load checkpoint into the ``init_params`` tree layout.

    With ``mesh`` + ``logical_axes``, each stacked tensor is placed with its
    NamedSharding as it is built, so host->HBM transfer happens shard-wise.
    ``quantize=True`` returns the int8 tree of ``ops.quant.quantize_params``,
    each tensor quantized as it reaches the device so that the full-precision
    model is never resident there (``quantize_params_streamed``).
    """
    import jax
    import jax.numpy as jnp

    from helix_tpu.models.llama import param_logical_axes
    from helix_tpu.parallel.sharding import _prune_spec_for_mesh, spec_for

    cfg = cfg or load_config(model_dir)
    dtype = np.dtype(dtype) if dtype is not None else np.dtype(cfg.dtype)
    if np.dtype(cfg.dtype) != dtype:
        import dataclasses as _dc

        cfg = _dc.replace(cfg, dtype=dtype.name)
    shards = _open_shards(model_dir)
    L = cfg.num_layers
    H, KVH, D, E = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.hidden_size

    def get(name):
        t = shards.get(name)
        if t.dtype != dtype:
            import ml_dtypes  # noqa: F401  (registers bfloat16 for numpy)

            t = t.astype(dtype)
        return t

    def linear_t(name):
        """HF Linear stores [out, in]; our convention is [in, out]."""
        return np.ascontiguousarray(get(name).T)

    def stack(fn):
        return np.stack([fn(i) for i in range(L)])

    pfx = "model.layers.{}."
    if cfg.is_mla:
        params = _deepseek_v2_tree(cfg, shards, get, linear_t)
        return cfg, _place_tree(params, cfg, mesh, logical_axes, quantize)
    if cfg.state_mixer == "deltanet":
        params = _qwen3_next_tree(cfg, get, linear_t)
        return cfg, _place_tree(params, cfg, mesh, logical_axes, quantize)
    fused_qkv = f"{pfx.format(0)}self_attn.qkv_proj.weight" in shards
    fused_mlp = f"{pfx.format(0)}mlp.gate_up_proj.weight" in shards

    def qkv(i):
        p = pfx.format(i) + "self_attn."
        if fused_qkv:
            w = linear_t(p + "qkv_proj.weight")  # [E, (H+2KVH)*D]
            return (
                w[:, : H * D],
                w[:, H * D : (H + KVH) * D],
                w[:, (H + KVH) * D :],
            )
        return (
            linear_t(p + "q_proj.weight"),
            linear_t(p + "k_proj.weight"),
            linear_t(p + "v_proj.weight"),
        )

    def gate_up(i):
        p = pfx.format(i) + "mlp."
        if fused_mlp:
            w = linear_t(p + "gate_up_proj.weight")  # [E, 2F]
            return w[:, : cfg.intermediate_size], w[:, cfg.intermediate_size :]
        return linear_t(p + "gate_proj.weight"), linear_t(p + "up_proj.weight")

    layers = {
        "attn_norm": {
            "weight": stack(lambda i: get(pfx.format(i) + "input_layernorm.weight"))
        },
        "mlp_norm": {
            "weight": stack(
                lambda i: get(pfx.format(i) + "post_attention_layernorm.weight")
            )
        },
        "wq": {"weight": stack(lambda i: qkv(i)[0])},
        "wk": {"weight": stack(lambda i: qkv(i)[1])},
        "wv": {"weight": stack(lambda i: qkv(i)[2])},
        "wo": {
            "weight": stack(
                lambda i: linear_t(pfx.format(i) + "self_attn.o_proj.weight")
            )
        },
        "w_gate": {"weight": stack(lambda i: gate_up(i)[0])},
        "w_up": {"weight": stack(lambda i: gate_up(i)[1])},
        "w_down": {
            "weight": stack(lambda i: linear_t(pfx.format(i) + "mlp.down_proj.weight"))
        },
    }
    if cfg.num_experts > 0:
        # Mixtral: block_sparse_moe.gate + experts.N.w1/w3/w2
        X = cfg.num_experts
        del layers["w_gate"], layers["w_up"], layers["w_down"]
        layers["router"] = {
            "weight": stack(
                lambda i: linear_t(
                    pfx.format(i) + "block_sparse_moe.gate.weight"
                )
            )
        }

        def experts(i, w):
            return np.stack([
                linear_t(
                    pfx.format(i)
                    + f"block_sparse_moe.experts.{e}.{w}.weight"
                )
                for e in range(X)
            ])

        layers["experts"] = {
            # HF Mixtral: w1 = gate, w3 = up, w2 = down
            "w_gate": {"weight": stack(lambda i: experts(i, "w1"))},
            "w_up": {"weight": stack(lambda i: experts(i, "w3"))},
            "w_down": {"weight": stack(lambda i: experts(i, "w2"))},
        }
    if cfg.attention_bias and f"{pfx.format(0)}self_attn.q_proj.bias" in shards:
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj")):
            layers[ours]["bias"] = stack(
                lambda i, t=theirs: get(pfx.format(i) + f"self_attn.{t}.bias")
            )
    if cfg.qk_norm:
        layers["q_norm"] = {
            "weight": stack(lambda i: get(pfx.format(i) + "self_attn.q_norm.weight"))
        }
        layers["k_norm"] = {
            "weight": stack(lambda i: get(pfx.format(i) + "self_attn.k_norm.weight"))
        }

    params = {
        "embed": {"weight": get("model.embed_tokens.weight")},
        "layers": layers,
        "final_norm": {"weight": get("model.norm.weight")},
    }
    if not cfg.tie_word_embeddings:
        if "lm_head.weight" in shards:
            params["lm_head"] = {"weight": linear_t("lm_head.weight")}
        else:  # some checkpoints tie implicitly
            params["lm_head"] = {
                "weight": np.ascontiguousarray(params["embed"]["weight"].T)
            }

    return cfg, _place_tree(params, cfg, mesh, logical_axes, quantize)


def _place_tree(params, cfg, mesh, logical_axes, quantize):
    """A host tree onto the device(s): sharded under a mesh, int8 tensor
    by tensor with ``quantize``."""
    import jax
    import jax.numpy as jnp

    from helix_tpu.models.llama import param_logical_axes
    from helix_tpu.parallel.sharding import _prune_spec_for_mesh, spec_for

    axes = None
    if mesh is not None:
        from jax.sharding import NamedSharding

        axes = logical_axes or param_logical_axes(cfg)

    def place(x, ax):
        if mesh is None:
            return jnp.asarray(x)
        spec = _prune_spec_for_mesh(mesh, spec_for(ax))
        return jax.device_put(x, NamedSharding(mesh, spec))

    if quantize:
        from helix_tpu.ops.quant import quantize_params_streamed

        return quantize_params_streamed(params, place, axes)
    if mesh is not None:
        return jax.tree.map(place, params, axes)
    return jax.tree.map(jnp.asarray, params)


def _deepseek_v2_tree(cfg, shards, get, linear_t):
    """DeepSeek-V2's tensor names (``model_type: deepseek_v2``, direct query
    projection) into the two-stack tree of ``init_params``: the leading
    dense layers under ``dense_layers``, the expert layers under ``layers``.
    Projections keep the published column order (rope pairs interleaved:
    the program rotates them as published)."""
    pfx = "model.layers.{}."
    n_dense = cfg.first_k_dense

    def stack_of(ids, moe):
        def st(fn):
            return np.stack([fn(i) for i in ids])

        def lin(name):
            return {"weight": st(lambda i: linear_t(pfx.format(i) + name))}

        def mlp(at):
            return {
                "w_gate": lin(at + "gate_proj.weight"),
                "w_up": lin(at + "up_proj.weight"),
                "w_down": lin(at + "down_proj.weight"),
            }

        lp = {
            "attn_norm": {"weight": st(
                lambda i: get(pfx.format(i) + "input_layernorm.weight"))},
            "mlp_norm": {"weight": st(lambda i: get(
                pfx.format(i) + "post_attention_layernorm.weight"))},
            "wq": lin("self_attn.q_proj.weight"),
            "wkv_a": lin("self_attn.kv_a_proj_with_mqa.weight"),
            "kv_norm": {"weight": st(lambda i: get(
                pfx.format(i) + "self_attn.kv_a_layernorm.weight"))},
            "wkv_b": lin("self_attn.kv_b_proj.weight"),
            "wo": lin("self_attn.o_proj.weight"),
        }
        if not moe:
            lp.update(mlp("mlp."))
            return lp
        lp["router"] = lin("mlp.gate.weight")
        lp["experts"] = {
            ours: {"weight": st(lambda i, t=theirs: np.stack([
                linear_t(pfx.format(i) + f"mlp.experts.{e}.{t}.weight")
                for e in range(cfg.num_experts)]))}
            for ours, theirs in (("w_gate", "gate_proj"),
                                 ("w_up", "up_proj"),
                                 ("w_down", "down_proj"))
        }
        if cfg.num_shared_experts:
            lp["shared"] = mlp("mlp.shared_experts.")
        return lp

    params = {
        "embed": {"weight": get("model.embed_tokens.weight")},
        "layers": stack_of(range(n_dense, cfg.num_layers), True),
        "final_norm": {"weight": get("model.norm.weight")},
    }
    if n_dense:
        params["dense_layers"] = stack_of(range(n_dense), False)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = {"weight": linear_t("lm_head.weight")}
    return params


def _qwen3_next_tree(cfg, get, linear_t):
    """Qwen3-Next's tensor names (``model_type: qwen3_next``) into the tree of
    ``init_params``: a stack a run of ``cfg.layer_runs()`` (the delta layers
    of every period under one key, the attention layers under another,
    repetition-major).  Three of the published file's projections are
    INTERLEAVED and are parted here:

    - ``linear_attn.in_proj_qkvz`` ``[2 nk dk + 2 nv dv, E]``: a key-head
      group's ``[q (dk) | k (dk) | v (r dv) | z (r dv)]``, ``r = nv / nk``
      value heads a key head, group after group: into ``in_qkv`` (columns
      ``q | k | v``, each heads-major: the order of the published ``conv1d``'s
      channels) and ``in_z``;
    - ``linear_attn.in_proj_ba`` ``[2 nv, E]``: a group's ``[b (r) | a (r)]``:
      into ``in_b`` and ``in_a``;
    - ``self_attn.q_proj`` ``[H * 2D, E]``: a head's ``[q (D) | gate (D)]``:
      into ``wq`` and ``attn_gate``.

    ``conv1d.weight [C, 1, K]`` is the taps ``[C, K]`` (the last tap the
    token's own).  Experts ``[lo, hi)`` of ``cfg.held_experts`` alone are
    read.  The multi-token-prediction module (``mtp.*``) is not loaded."""
    pfx = "model.layers.{}."
    nk, nv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
    r = nv // nk
    H, D, E = cfg.num_heads, cfg.head_dim, cfg.hidden_size
    lo, hi = cfg.held_experts or (0, cfg.num_experts)

    def delta(i):
        at = pfx.format(i) + "linear_attn."
        qkvz = linear_t(at + "in_proj_qkvz.weight").reshape(
            E, nk, 2 * dk + 2 * r * dv)
        q, k, v, z = np.split(
            qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
        ba = linear_t(at + "in_proj_ba.weight").reshape(E, nk, 2 * r)
        flat = lambda t: np.ascontiguousarray(t.reshape(E, -1))
        return {
            "in_qkv": {"weight": np.concatenate(
                [flat(q), flat(k), flat(v)], axis=-1)},
            "in_z": {"weight": flat(z)},
            "in_b": {"weight": flat(ba[..., :r])},
            "in_a": {"weight": flat(ba[..., r:])},
            "conv": {"taps": get(at + "conv1d.weight")[:, 0, :]},
            "A_log": {"bias": get(at + "A_log").astype(np.float32)},
            "dt_bias": {"bias": get(at + "dt_bias").astype(np.float32)},
            "o_norm": {"weight": get(at + "norm.weight")},
            "out_proj": {"weight": linear_t(at + "out_proj.weight")},
        }

    def attn(i):
        at = pfx.format(i) + "self_attn."
        qg = linear_t(at + "q_proj.weight").reshape(E, H, 2 * D)
        return {
            "wq": {"weight": np.ascontiguousarray(
                qg[..., :D].reshape(E, H * D))},
            "attn_gate": {"weight": np.ascontiguousarray(
                qg[..., D:].reshape(E, H * D))},
            "wk": {"weight": linear_t(at + "k_proj.weight")},
            "wv": {"weight": linear_t(at + "v_proj.weight")},
            "wo": {"weight": linear_t(at + "o_proj.weight")},
            "q_norm": {"weight": get(at + "q_norm.weight")},
            "k_norm": {"weight": get(at + "k_norm.weight")},
        }

    def experts(i):
        at = pfx.format(i) + "mlp."
        names = (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                 ("w_down", "down_proj"))
        return {
            "router": {"weight": linear_t(at + "gate.weight")},
            "experts": {ours: {"weight": np.stack([
                linear_t(at + f"experts.{e}.{theirs}.weight")
                for e in range(lo, hi)])} for ours, theirs in names},
            "shared": {ours: {"weight": linear_t(
                at + f"shared_expert.{theirs}.weight")}
                for ours, theirs in names},
            "shared_gate": {"weight": linear_t(
                at + "shared_expert_gate.weight")},
        }

    def layer(i, mixer):
        return {
            "attn_norm": {"weight": get(
                pfx.format(i) + "input_layernorm.weight")},
            "mlp_norm": {"weight": get(
                pfx.format(i) + "post_attention_layernorm.weight")},
            **(delta(i) if mixer == "deltanet" else attn(i)),
            **experts(i),
        }

    def stacked(trees):
        import jax

        return jax.tree.map(lambda *a: np.stack(a), *trees)

    params = {
        "embed": {"weight": get("model.embed_tokens.weight")},
        "final_norm": {"weight": get("model.norm.weight")},
        "lm_head": {"weight": linear_t("lm_head.weight")},
    }
    at = 0
    for group in cfg.layer_runs():
        span = sum(run.count for run in group.runs)
        off = 0
        for run in group.runs:
            ids = [at + rep * span + off + n
                   for rep in range(group.reps) for n in range(run.count)]
            params[run.key] = stacked([layer(i, run.mixer) for i in ids])
            off += run.count
        at += group.reps * span
    return params
