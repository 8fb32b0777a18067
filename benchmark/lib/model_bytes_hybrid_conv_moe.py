"""Operations and bytes of a hybrid decoder of gated short convolutions,
GQA layers and routed experts (LFM2 family), from a configuration's sizes.

Everything is taken from the configuration's JSON file (Hugging Face key
names) and the serving settings beside it: nothing is read from the
program.  What a roofline share needs: the least bytes a decode step reads
given how many experts it touched, and the operations and bytes of one call
of each kernel the cell runs at this geometry (the dense paged attention at
32 / 8 heads of width 64, the grouped expert product at 32 x 2048 x 1792)
given its rows and contexts.
"""

from benchmark.lib.model_bytes_mla_moe import (  # noqa: F401
    _DTYPE_BYTES, _matrix, roofline_share,
)


def _sizes(cfg):
    E, H = cfg["hidden_size"], cfg["num_attention_heads"]
    types = cfg["layer_types"]
    return dict(
        E=E, H=H, KVH=cfg["num_key_value_heads"],
        D=cfg.get("head_dim") or E // H, K=cfg["conv_L_cache"],
        F=cfg["intermediate_size"], Fx=cfg["moe_intermediate_size"],
        X=cfg["num_experts"], k=cfg["num_experts_per_tok"],
        V=cfg["vocab_size"], L=cfg["num_hidden_layers"],
        conv=types.count("conv"), attn=types.count("full_attention"),
        dense=cfg.get("num_dense_layers", 0),
    )


def parameter_count(cfg):
    """Parameters by part (the tied head is the embedding, counted once)."""
    s = _sizes(cfg)
    E, moe = s["E"], s["L"] - s["dense"]
    parts = {
        "conv_operators": s["conv"] * (3 * E * E + E * E + E * s["K"]),
        "attention_operators": s["attn"] * (
            2 * E * s["H"] * s["D"] + 2 * E * s["KVH"] * s["D"]
            + 2 * s["D"]),
        "dense_mlp": s["dense"] * 3 * E * s["F"],
        "router": moe * (E * s["X"] + (s["X"] if cfg.get(
            "use_expert_bias") else 0)),
        "routed_experts": moe * s["X"] * 3 * E * s["Fx"],
        "embedding": s["V"] * E,
        "norms": s["L"] * 2 * E + E,
    }
    parts["total"] = sum(parts.values())
    return parts


def weight_bytes_by_part(cfg, weight_dtype="int8", act_dtype="bfloat16"):
    """Bytes of the weights as served, by part: a matrix at the weight
    dtype (int8 with an f32 scale a column), the taps, the norms in the
    activations' dtype, the expert bias in f32."""
    s = _sizes(cfg)
    wb, ab = _DTYPE_BYTES[weight_dtype], _DTYPE_BYTES[act_dtype]
    E, moe = s["E"], s["L"] - s["dense"]
    expert = 2 * _matrix(E, s["Fx"], wb) + _matrix(s["Fx"], E, wb)
    parts = {
        "embedding": s["V"] * E * wb + (s["V"] * 4 if wb == 1 else 0),
        "conv_operators": s["conv"] * (
            _matrix(E, 3 * E, wb) + _matrix(E, E, wb) + E * s["K"] * ab),
        "attention_operators": s["attn"] * (
            2 * _matrix(E, s["H"] * s["D"], wb)
            + 2 * _matrix(E, s["KVH"] * s["D"], wb) + 2 * s["D"] * ab),
        "dense_mlp": s["dense"] * (
            2 * _matrix(E, s["F"], wb) + _matrix(s["F"], E, wb)),
        "router": moe * (_matrix(E, s["X"], wb) + (
            s["X"] * 4 if cfg.get("use_expert_bias") else 0)),
        "routed_experts": moe * s["X"] * expert,
        "norms": (s["L"] * 2 * E + E) * ab,
    }
    parts["one_expert"] = expert
    parts["total"] = sum(v for k, v in parts.items() if k != "one_expert")
    return parts


def kv_bytes_per_token(cfg, kv_dtype="bfloat16"):
    """K and V of one token over the layers that HAVE pages, as stored:
    two 64-wide kv heads share a 128-lane tile, so nothing is padded."""
    s = _sizes(cfg)
    return s["attn"] * 2 * s["KVH"] * s["D"] * _DTYPE_BYTES[kv_dtype]


def page_bytes(cfg, page_size, kv_dtype="bfloat16"):
    return kv_bytes_per_token(cfg, kv_dtype) * page_size


def state_bytes_per_slot(cfg, act_dtype="bfloat16"):
    """The conv state of one sequence: its last K - 1 gated inputs a layer."""
    s = _sizes(cfg)
    return s["conv"] * (s["K"] - 1) * s["E"] * _DTYPE_BYTES[act_dtype]


def decode_step_bytes(cfg, live_context_tokens, experts_touched, rows,
                      weight_dtype="int8", kv_dtype="bfloat16"):
    """Least bytes one decode step of ``rows`` sequences reads from HBM:
    every matrix outside the routed experts once (the embedding table by
    ``rows`` rows, and whole again as the tied head), ``experts_touched``
    experts of each expert layer (the mean over the layers of the distinct
    experts the step routed to), the K and V of ``live_context_tokens``
    tokens summed over the batch, and the rows' conv states."""
    s = _sizes(cfg)
    p = weight_bytes_by_part(cfg, weight_dtype)
    wb = _DTYPE_BYTES[weight_dtype]
    fixed = p["total"] - p["routed_experts"] + rows * s["E"] * wb
    return (fixed
            + (s["L"] - s["dense"]) * experts_touched * p["one_expert"]
            + live_context_tokens * kv_bytes_per_token(cfg, kv_dtype)
            + rows * state_bytes_per_slot(cfg))


def paged_kernel_call(cfg, q_lens, contexts, kv_dtype="bfloat16",
                      act_dtype="bfloat16"):
    """``(operations, bytes)`` of ONE call (one attention layer) of the
    dense ragged paged attention.  ``q_lens[r]`` fresh tokens of row r,
    ``contexts[r]`` its history tokens in the page pool.  Operations: for
    every (query, key) pair and query head a score and a value product over
    the head width, 2 a multiply-add (what the algorithm needs: the kernel
    multiplies each query against its neighbour kv head's lanes too, which
    are zeros).  Bytes, the least: each row's history once, the fresh keys
    and values, the queries in, the output out."""
    s = _sizes(cfg)
    kb, ab = _DTYPE_BYTES[kv_dtype], _DTYPE_BYTES[act_dtype]
    pairs = sum(q * c + q * (q + 1) / 2 for q, c in zip(q_lens, contexts))
    ops = 2 * s["H"] * pairs * 2 * s["D"]
    tokens = sum(q_lens)
    kv_w = 2 * s["KVH"] * s["D"]
    bytes_ = (sum(contexts) * kv_w * kb + tokens * kv_w * ab
              + 2 * tokens * s["H"] * s["D"] * ab)
    return ops, bytes_


def grouped_expert_product(cfg, routed_rows, experts_touched,
                           weight_dtype="int8", act_dtype="bfloat16"):
    """``(operations, bytes)`` of one expert layer's three grouped products
    (gate, up, down) over ``routed_rows`` (token, choice) assignments that
    reach ``experts_touched`` distinct experts: operations follow the rows,
    weight bytes the experts touched."""
    s = _sizes(cfg)
    ab = _DTYPE_BYTES[act_dtype]
    ops = 3 * 2 * routed_rows * s["E"] * s["Fx"]
    p = weight_bytes_by_part(cfg, weight_dtype)
    bytes_ = (experts_touched * p["one_expert"]
              + routed_rows * (2 * s["E"] + 3 * s["Fx"]) * ab)
    return ops, bytes_
