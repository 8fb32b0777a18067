"""Operations and bytes of a hybrid decoder of gated DELTA-RULE layers beside
LATENT-attention layers with a compressed, gated query, whose routed experts
are HELD in part (one expert-parallel rank's), from a configuration's sizes.

Everything is taken from the configuration's JSON file (Hugging Face key
names; ``n_routed_experts`` is what is loaded, ``published_n_routed_experts``
what the router scores) and the ``serving`` settings beside it: nothing is
read from the program.  What a roofline share needs: the bytes of the
weights, of a page of latent and of one sequence's state as served, the least
bytes a decode step moves, and the operations and bytes of one call (one
layer) of the delta-rule decode kernel and of its chunked form.

A delta layer's state a slot: ``S [value heads, dk, dv]`` float32 and the
conv tail ``[K - 1, 2 * key heads * dk + value heads * dv]`` in the
activations' dtype.
"""

from benchmark.lib.model_bytes_mla_moe import (  # noqa: F401
    ROPE_LANES, _DTYPE_BYTES, _matrix, roofline_share,
)

CHUNK = 64      # tokens the chunked form takes at a time


def _sizes(cfg):
    full = set(cfg["full_attention_layers"])
    L = cfg["num_hidden_layers"]
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return dict(
        E=cfg["hidden_size"], H=cfg["num_attention_heads"],
        R=cfg["kv_lora_rank"], Rq=cfg["q_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv_attn=cfg["v_head_dim"], F=cfg["intermediate_size"],
        Fx=cfg["moe_intermediate_size"], X=cfg["n_routed_experts"],
        X_all=cfg.get("published_n_routed_experts", cfg["n_routed_experts"]),
        shared=cfg.get("n_shared_experts") or 0, V=cfg["vocab_size"], L=L,
        latent=len([l for l in range(L) if l in full]),
        delta=len([l for l in range(L) if l not in full]),
        dense=cfg.get("first_k_dense_replace", 0),
        nk=nk, nv=nv, dk=dk, dv=dv, K=cfg["linear_conv_kernel_dim"],
        C=2 * nk * dk + nv * dv,
    )


def parameter_count(cfg):
    """Parameters by part, over the layers and experts held (matrices and
    the small vectors beside them)."""
    s = _sizes(cfg)
    E = s["E"]
    delta = (E * s["C"] + 2 * E * s["nv"] * s["dv"] + 2 * E * s["nv"]
             + s["C"] * s["K"] + 2 * s["nv"] + s["dv"])
    qk = s["H"] * (s["dn"] + s["dr"])
    latent = (E * s["Rq"] + s["Rq"] + s["Rq"] * qk + E * (s["R"] + s["dr"])
              + s["R"] + s["R"] * s["H"] * (s["dn"] + s["dv_attn"])
              + 2 * s["H"] * s["dv_attn"] * E)
    moe_layers = s["L"] - s["dense"]
    parts = {
        "delta_mixers": s["delta"] * delta,
        "latent_mixers": s["latent"] * latent,
        "dense_mlp": s["dense"] * 3 * E * s["F"],
        "held_experts": moe_layers * s["X"] * 3 * E * s["Fx"],
        "shared_experts": moe_layers * 3 * E * s["shared"] * s["Fx"],
        "routers": moe_layers * (E * s["X_all"] + s["X_all"]),
        "embedding": s["V"] * E,
        "head": 0 if cfg.get("tie_word_embeddings") else s["V"] * E,
        "norms": s["L"] * 4 * E + E,
    }
    parts["total"] = sum(parts.values())
    return parts


def weight_bytes_by_part(cfg, weight_dtype="int8", act_dtype="bfloat16"):
    """Bytes of the weights as served, by part: a matrix at the weight dtype
    (int8 with an f32 scale a column; the embedding a scale a row), norms
    and conv taps in the activations' dtype, ``A_log``, ``dt_bias`` and the
    selection bias in f32."""
    s = _sizes(cfg)
    wb, ab = _DTYPE_BYTES[weight_dtype], _DTYPE_BYTES[act_dtype]
    E = s["E"]
    m = lambda r, c: _matrix(r, c, wb)
    delta = (m(E, s["C"]) + 2 * m(E, s["nv"]) + m(E, s["nv"] * s["dv"])
             + m(s["nv"] * s["dv"], E) + s["C"] * s["K"] * ab
             + 2 * s["nv"] * 4 + s["dv"] * ab)
    qk = s["H"] * (s["dn"] + s["dr"])
    hv = s["H"] * s["dv_attn"]
    latent = (m(E, s["Rq"]) + s["Rq"] * ab + m(s["Rq"], qk)
              + m(E, s["R"] + s["dr"]) + s["R"] * ab
              + m(s["R"], s["H"] * (s["dn"] + s["dv_attn"]))
              + m(E, hv) + m(hv, E))
    expert = 2 * m(E, s["Fx"]) + m(s["Fx"], E)
    Fs = s["shared"] * s["Fx"]
    moe_layers = s["L"] - s["dense"]
    table = s["V"] * E * wb + (s["V"] * 4 if wb == 1 else 0)
    parts = {
        "delta_mixers": s["delta"] * delta,
        "latent_mixers": s["latent"] * latent,
        "dense_mlp": s["dense"] * (2 * m(E, s["F"]) + m(s["F"], E)),
        "held_experts": moe_layers * s["X"] * expert,
        "shared_experts": moe_layers * (2 * m(E, Fs) + m(Fs, E)),
        "routers": moe_layers * (m(E, s["X_all"]) + s["X_all"] * 4),
        "embedding": table,
        "head": 0 if cfg.get("tie_word_embeddings") else table,
        "norms": (s["L"] * 4 * E + E) * ab,
    }
    parts["total"] = sum(parts.values())
    return parts


def weight_bytes(cfg, weight_dtype="int8"):
    return weight_bytes_by_part(cfg, weight_dtype)["total"]


def state_bytes_per_slot_layer(cfg, act_dtype="bfloat16"):
    """One sequence's state in one delta layer: ``S`` of every value head in
    float32 and the conv tail."""
    s = _sizes(cfg)
    return (s["nv"] * s["dk"] * s["dv"] * 4
            + (s["K"] - 1) * s["C"] * _DTYPE_BYTES[act_dtype])


def state_bytes_per_slot(cfg, act_dtype="bfloat16"):
    """One sequence's state, all delta layers: what a decode slot holds
    whatever the sequence's length."""
    return _sizes(cfg)["delta"] * state_bytes_per_slot_layer(cfg, act_dtype)


def page_bytes(cfg, page_size, kv_dtype="bfloat16"):
    """One page of the latent pool over the latent layers, as allocated: the
    latent and the rope key in a 128-lane slot."""
    s = _sizes(cfg)
    return (s["latent"] * page_size * (s["R"] + ROPE_LANES)
            * _DTYPE_BYTES[kv_dtype])


def decode_step_bytes(cfg, rows, live_context_tokens, experts_touched=None,
                      weight_dtype="int8", kv_dtype="bfloat16"):
    """Least bytes one decode step of ``rows`` live sequences moves: every
    matrix once (the embedding table by ``rows`` rows; of the held experts
    those ``experts_touched`` a layer, all if None), each row's delta state
    read once and written once, and the live tokens' latent and rope key
    (512 + 64 values a token and latent layer) read once."""
    s = _sizes(cfg)
    p = weight_bytes_by_part(cfg, weight_dtype)
    wb = _DTYPE_BYTES[weight_dtype]
    experts = p["held_experts"]
    if experts_touched is not None:
        experts = experts * experts_touched / s["X"]
    latent = (live_context_tokens * s["latent"] * (s["R"] + s["dr"])
              * _DTYPE_BYTES[kv_dtype])
    return (p["total"] - p["embedding"] - p["held_experts"] + experts
            + rows * s["E"] * wb + 2 * rows * state_bytes_per_slot(cfg)
            + latent)


def deltanet_decode_call(cfg, rows):
    """``(operations, bytes)`` of ONE call (one layer) of the decode kernel
    over ``rows`` live rows.  Bytes, the least: ``S`` read once and written
    once a row, and the five vectors a head in and one out (q, k, v, the
    decay and beta across the lanes, o: under 3% of it, counted).
    Operations: a state entry is decayed, read under ``k``, written and read
    under ``q``: ``1 + 2 + 2 + 2`` an entry, all on the vector unit (no
    matrix product: the operations' bound is far under the bytes')."""
    s = _sizes(cfg)
    entries = rows * s["nv"] * s["dk"] * s["dv"]
    return 7 * entries, 2 * entries * 4 + rows * s["nv"] * 6 * s["dv"] * 4


def deltanet_chunk_call(cfg, tokens):
    """``(operations, bytes)`` of ONE call (one layer) of the chunked form
    over one row of ``tokens`` fresh tokens, 64 at a time (2 operations a
    multiply-add).  A chunk of ``C`` tokens a value head: ``k k^T`` and ``q
    k^T`` (``2 C^2 dk``), the unit lower triangular solve against ``dv +
    dk`` columns (``C^2 (dv + dk) / 2``), the writes less what the state held
    (``C dk dv``), the state's and the chunk's share of the output (``C dk
    dv + C^2 dv``) and the new state (``C dk dv``).  Bytes, the least: the
    state read once and written once, q, k, v, g and beta in and o out."""
    s = _sizes(cfg)
    C, dk, dv = CHUNK, s["dk"], s["dv"]
    chunks = -(-tokens // C)
    mults = (2 * C * C * dk + C * C * (dv + dk) // 2 + 3 * C * dk * dv
             + C * C * dv)
    ops = 2 * mults * chunks * s["nv"]
    bytes_ = (2 * s["nv"] * dk * dv * 4
              + tokens * s["nv"] * (2 * dk + 2 * dv + 2) * 4)
    return ops, bytes_
