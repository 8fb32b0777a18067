"""Operations and bytes of a decoder of ONE-BRANCH layers: Mamba-2 mixers,
attention over pages, and routed experts that are ungated MLPs in a LATENT
narrower than the model and HELD in part (one expert-parallel rank's), from a
configuration's sizes.

Everything is taken from the configuration's JSON file (Hugging Face key
names; ``n_routed_experts`` is what is loaded, ``published_n_routed_experts``
what the router scores; ``hybrid_override_pattern`` the kept layers, one
character a layer) and the ``serving`` settings beside it: nothing is read
from the program.  What a roofline share needs: the bytes of the weights, of
a page of K/V and of one sequence's state as served, the least bytes a decode
step moves, and the operations and bytes of one call (one layer) of the
state-space decode kernel, of its chunked form and of one grouped product.

A Mamba-2 layer's state a slot: ``h [heads, head dim, state]`` float32 and the
conv tail ``[K - 1, heads * head dim + 2 * groups * state]`` in the
activations' dtype.
"""

from benchmark.lib.model_bytes_mla_moe import (  # noqa: F401
    _DTYPE_BYTES, _matrix, roofline_share,
)


def _sizes(cfg):
    pat = cfg["hybrid_override_pattern"]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return dict(
        E=cfg["hidden_size"], Hq=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], d=cfg["head_dim"],
        F=cfg["intermediate_size"], Fx=cfg["moe_intermediate_size"],
        Fs=(cfg.get("n_shared_experts") or 0)
        * cfg["moe_shared_expert_intermediate_size"],
        Z=cfg.get("moe_latent_size") or cfg["hidden_size"],
        X=cfg["n_routed_experts"],
        X_all=cfg.get("published_n_routed_experts", cfg["n_routed_experts"]),
        k=cfg["num_experts_per_tok"], V=cfg["vocab_size"],
        mamba=pat.count("M"), attn=pat.count("*"), moe=pat.count("E"),
        dense=pat.count("-"), H=H, P=P, G=G, N=N, K=cfg["conv_kernel"],
        inner=H * P, C=H * P + 2 * G * N, chunk=cfg.get("chunk_size", 128),
    )


def parameter_count(cfg):
    """Parameters by part, over the layers and experts held (matrices and
    the small vectors beside them; a layer's one norm with it)."""
    s = _sizes(cfg)
    E = s["E"]
    mamba = (E * (2 * s["inner"] + 2 * s["G"] * s["N"] + s["H"])   # W_in
             + s["C"] * (s["K"] + 1)                      # conv taps and bias
             + 3 * s["H"]                                 # A_log, dt_bias, D
             + s["inner"]                                 # the gated norm
             + s["inner"] * E + E)                        # W_out, the norm
    attn = (E * (s["Hq"] + 2 * s["Hkv"]) * s["d"] + s["Hq"] * s["d"] * E
            + E)
    beside = (E * s["X_all"] + s["X_all"]                 # router and bias
              + 2 * E * s["Z"]                            # fc1, fc2
              + 2 * E * s["Fs"] + E)                      # shared, the norm
    parts = {
        "mamba_mixers": s["mamba"] * mamba,
        "attention": s["attn"] * attn,
        "expert_layers_beside_the_routed": s["moe"] * beside,
        "held_experts": s["moe"] * s["X"] * 2 * s["Z"] * s["Fx"],
        "dense_mlp": s["dense"] * (2 * E * s["F"] + E),
        "embedding": s["V"] * E,
        "head": 0 if cfg.get("tie_word_embeddings") else s["V"] * E,
        "final_norm": E,
    }
    parts["one_expert"] = 2 * s["Z"] * s["Fx"]
    parts["total"] = sum(v for n, v in parts.items() if n != "one_expert")
    return parts


def published_parameter_count(cfg):
    """``parameter_count`` of the WHOLE published model (the ``published_*``
    keys beside the reduced ones), and the parameters a token uses at the
    published top-k."""
    whole = dict(cfg)
    for key in cfg.get("reduced", []):
        whole[key] = cfg["published_" + key]
    parts = parameter_count(whole)
    s = _sizes(whole)
    active = parts["total"] - parts["held_experts"] + (
        s["moe"] * s["k"] * parts["one_expert"])
    return parts, active


def weight_bytes_by_part(cfg, weight_dtype="int8", act_dtype="bfloat16"):
    """Bytes of the weights as served, by part: a matrix at the weight dtype
    (int8 with an f32 scale a column; the embedding a scale a row; the
    in-projection as its three parts), norms, conv taps and bias in the
    activations' dtype, ``A_log``, ``dt_bias``, ``D`` and the selection bias
    in f32."""
    s = _sizes(cfg)
    wb, ab = _DTYPE_BYTES[weight_dtype], _DTYPE_BYTES[act_dtype]
    E = s["E"]
    m = lambda r, c: _matrix(r, c, wb)
    mamba = (m(E, s["inner"]) + m(E, s["C"]) + m(E, s["H"])
             + m(s["inner"], E) + s["C"] * (s["K"] + 1) * ab
             + 3 * s["H"] * 4 + (s["inner"] + E) * ab)
    attn = (m(E, s["Hq"] * s["d"]) + 2 * m(E, s["Hkv"] * s["d"])
            + m(s["Hq"] * s["d"], E) + E * ab)
    beside = (m(E, s["X_all"]) + s["X_all"] * 4 + m(E, s["Z"])
              + m(s["Z"], E) + m(E, s["Fs"]) + m(s["Fs"], E) + E * ab)
    expert = m(s["Z"], s["Fx"]) + m(s["Fx"], s["Z"])
    table = s["V"] * E * wb + (s["V"] * 4 if wb == 1 else 0)
    parts = {
        "mamba_mixers": s["mamba"] * mamba,
        "attention": s["attn"] * attn,
        "expert_layers_beside_the_routed": s["moe"] * beside,
        "held_experts": s["moe"] * s["X"] * expert,
        "dense_mlp": s["dense"] * (m(E, s["F"]) + m(s["F"], E) + E * ab),
        "embedding": table,
        "head": 0 if cfg.get("tie_word_embeddings") else table,
        "final_norm": E * ab,
    }
    parts["one_expert"] = expert
    parts["total"] = sum(v for n, v in parts.items() if n != "one_expert")
    return parts


def weight_bytes(cfg, weight_dtype="int8"):
    return weight_bytes_by_part(cfg, weight_dtype)["total"]


def state_bytes_per_slot_layer(cfg, act_dtype="bfloat16"):
    """One sequence's state in one Mamba-2 layer: ``h`` of every head in
    float32 and the conv tail."""
    s = _sizes(cfg)
    return (s["H"] * s["P"] * s["N"] * 4
            + (s["K"] - 1) * s["C"] * _DTYPE_BYTES[act_dtype])


def state_bytes_per_slot(cfg, act_dtype="bfloat16"):
    """One sequence's state, all Mamba-2 layers: what a decode slot holds
    whatever the sequence's length."""
    return _sizes(cfg)["mamba"] * state_bytes_per_slot_layer(cfg, act_dtype)


def kv_bytes_per_token(cfg, kv_dtype="bfloat16"):
    """K and V of one token over the attention layers."""
    s = _sizes(cfg)
    return s["attn"] * 2 * s["Hkv"] * s["d"] * _DTYPE_BYTES[kv_dtype]


def page_bytes(cfg, page_size, kv_dtype="bfloat16"):
    return kv_bytes_per_token(cfg, kv_dtype) * page_size


def experts_touched(cfg, rows):
    """Held experts a layer that ``rows`` tokens reach, in expectation, if
    every published expert is as likely as another: a held expert is missed by
    one token with probability ``1 - k / X_all``."""
    s = _sizes(cfg)
    return s["X"] * (1.0 - (1.0 - s["k"] / s["X_all"]) ** rows)


def decode_step_bytes(cfg, rows, live_context_tokens, touched=None,
                      weight_dtype="int8", kv_dtype="bfloat16"):
    """Least bytes one decode step of ``rows`` live sequences moves: every
    matrix once (the embedding table by ``rows`` rows; of the held experts
    those ``touched`` a layer, ``experts_touched`` if None), each row's
    Mamba-2 state read once and written once, and the live tokens' K and V
    read once."""
    s = _sizes(cfg)
    p = weight_bytes_by_part(cfg, weight_dtype)
    wb = _DTYPE_BYTES[weight_dtype]
    if touched is None:
        touched = experts_touched(cfg, rows)
    return (p["total"] - p["embedding"] - p["held_experts"]
            + s["moe"] * touched * p["one_expert"]
            + rows * s["E"] * wb + 2 * rows * state_bytes_per_slot(cfg)
            + live_context_tokens * kv_bytes_per_token(cfg, kv_dtype))


def ssd_decode_call(cfg, rows):
    """``(operations, bytes)`` of ONE call (one layer) of the decode kernel
    over ``rows`` live rows.  Bytes, the least: ``h`` read once and written
    once a row, and the four vectors a packed row of heads in (``dt x``, the
    decay across the lanes, ``B`` and ``C`` of the row's group) and ``y`` out,
    128 lanes of float32 each for every ``128 / head dim`` heads: 0.6% of the
    state's, counted.  Operations: a state entry is decayed, written (a
    product and a sum) and read out (a product and a sum): 5 an entry, all on
    the vector unit (no matrix product: the operations' bound is far under
    the bytes')."""
    s = _sizes(cfg)
    entries = rows * s["H"] * s["P"] * s["N"]
    packed_rows = rows * s["H"] * s["P"] // 128
    return 5 * entries, 2 * entries * 4 + packed_rows * 5 * 128 * 4


def ssd_chunk_call(cfg, tokens):
    """``(operations, bytes)`` of ONE call (one layer) of the chunked form
    over one row of ``tokens`` fresh tokens, ``chunk_size`` at a time (2
    operations a multiply-add).  A block of ``C`` tokens: ``C B^T`` a group
    (``C^2 N``), its product with ``dt x`` under the decay a head (``C^2
    P``), the state's share of the output (``C P N``) and the new state (``C
    P N``).  Bytes, the least: the state read once and written once, x, B, C,
    dt in and y out in float32."""
    s = _sizes(cfg)
    C = s["chunk"]
    blocks = -(-tokens // C)
    mults = (s["G"] * C * C * s["N"]
             + s["H"] * (C * C * s["P"] + 2 * C * s["P"] * s["N"]))
    bytes_ = (2 * s["H"] * s["P"] * s["N"] * 4
              + tokens * (2 * s["inner"] + 2 * s["G"] * s["N"]
                          + 2 * s["H"]) * 4)
    return 2 * mults * blocks, bytes_


def grouped_product_call(cfg, rows, touched=None, weight_dtype="int8",
                         act_dtype="bfloat16"):
    """``(operations, bytes)`` of one expert layer's TWO grouped products
    (``relu(x W_up) ** 2`` in one call, ``W_down`` in the second) over
    ``rows`` sorted assignments that stay on this rank: 2 operations a
    multiply-add over ``[Z, Fx]`` and ``[Fx, Z]``; the weights of the
    ``touched`` held experts once (all of them if None), the rows in and out
    of each call in the activations' dtype (the second call's out in
    float32)."""
    s = _sizes(cfg)
    wb, ab = _DTYPE_BYTES[weight_dtype], _DTYPE_BYTES[act_dtype]
    touched = s["X"] if touched is None else touched
    one = _matrix(s["Z"], s["Fx"], wb) + _matrix(s["Fx"], s["Z"], wb)
    ops = 2 * rows * 2 * s["Z"] * s["Fx"]
    return ops, touched * one + rows * (
        s["Z"] * ab + 2 * s["Fx"] * ab + s["Z"] * 4)
