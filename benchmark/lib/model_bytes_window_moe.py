"""Operations and bytes of a decoder of SLIDING-WINDOW attention layers (a
ring of the last ``W`` tokens' K/V a sequence) beside FULL-attention layers
(pages), each kind with its own count of query heads, a gate a head, and
routed experts HELD in part (one expert-parallel rank's), from a
configuration's sizes.

Everything is taken from the configuration's JSON file (Hugging Face key
names; ``num_experts`` is what is loaded, ``published_num_experts`` what the
router scores) and the ``serving`` settings beside it: nothing is read from
the program.  What a roofline share needs: the bytes of the weights, of a
page of the full layers and of one sequence's rings as served, the least
bytes a decode step moves, and the operations and bytes of one call (one
layer) of the window kernel and of the full layers' paged call, for decode
rows and for a chunk.
"""

from benchmark.lib.model_bytes_mla_moe import (  # noqa: F401
    _DTYPE_BYTES, _matrix, roofline_share,
)


def _sizes(cfg):
    types = cfg["layer_types"]
    heads = cfg.get("num_attention_heads_per_layer") or (
        [cfg["num_attention_heads"]] * len(types))
    of = lambda kind: [h for h, t in zip(heads, types) if t == kind]  # noqa
    mlp = cfg.get("mlp_layer_types") or ["sparse"] * len(types)
    return dict(
        E=cfg["hidden_size"], D=cfg["head_dim"],
        KV=cfg["num_key_value_heads"], W=cfg["sliding_window"],
        H_full=(of("full_attention") or [0])[0],
        H_win=(of("sliding_attention") or [0])[0],
        full=types.count("full_attention"),
        window=types.count("sliding_attention"),
        gate=bool(cfg.get("gating")),
        F=cfg["intermediate_size"], Fx=cfg["moe_intermediate_size"],
        Fs=cfg.get("shared_expert_intermediate_size") or 0,
        X=cfg["num_experts"],
        X_all=cfg.get("published_num_experts", cfg["num_experts"]),
        k=cfg["num_experts_per_tok"], V=cfg["vocab_size"], L=len(types),
        dense=mlp.count("dense"),
    )


def _attention_parameters(s, H):
    """One layer's attention at ``H`` query heads: q, k, v, o and the gate."""
    E, D, KV = s["E"], s["D"], s["KV"]
    return 2 * E * H * D + 2 * E * KV * D + (E * H if s["gate"] else 0)


def parameter_count(cfg):
    """Parameters by part, over the layers and experts held."""
    s = _sizes(cfg)
    E = s["E"]
    sparse = s["L"] - s["dense"]
    parts = {
        "window_attention": s["window"] * _attention_parameters(
            s, s["H_win"]),
        "full_attention": s["full"] * _attention_parameters(s, s["H_full"]),
        "dense_mlp": s["dense"] * 3 * E * s["F"],
        "held_experts": sparse * s["X"] * 3 * E * s["Fx"],
        "shared_experts": sparse * 3 * E * s["Fs"],
        "routers": sparse * E * s["X_all"],
        "embedding": s["V"] * E,
        "head": 0 if cfg.get("tie_word_embeddings") else s["V"] * E,
        "norms": s["L"] * 2 * E + E,
    }
    parts["total"] = sum(parts.values())
    return parts


def weight_bytes_by_part(cfg, weight_dtype="int8", act_dtype="bfloat16"):
    """Bytes of the weights as served, by part: a matrix at the weight dtype
    (int8 with an f32 scale a column; the embedding a scale a row), norms in
    the activations' dtype."""
    s = _sizes(cfg)
    wb, ab = _DTYPE_BYTES[weight_dtype], _DTYPE_BYTES[act_dtype]
    E, D, KV = s["E"], s["D"], s["KV"]
    m = lambda r, c: _matrix(r, c, wb)                         # noqa: E731

    def attention(H):
        return (m(E, H * D) + 2 * m(E, KV * D) + m(H * D, E)
                + (m(E, H) if s["gate"] else 0))

    expert = 2 * m(E, s["Fx"]) + m(s["Fx"], E)
    sparse = s["L"] - s["dense"]
    table = s["V"] * E * wb + (s["V"] * 4 if wb == 1 else 0)
    parts = {
        "window_attention": s["window"] * attention(s["H_win"]),
        "full_attention": s["full"] * attention(s["H_full"]),
        "dense_mlp": s["dense"] * (2 * m(E, s["F"]) + m(s["F"], E)),
        "held_experts": sparse * s["X"] * expert,
        "shared_experts": sparse * (
            2 * m(E, s["Fs"]) + m(s["Fs"], E)) if s["Fs"] else 0,
        "routers": sparse * m(E, s["X_all"]),
        "embedding": table,
        "head": 0 if cfg.get("tie_word_embeddings") else m(E, s["V"]),
        "norms": (s["L"] * 2 * E + E) * ab,
    }
    parts["total"] = sum(parts.values())
    return parts


def weight_bytes(cfg, weight_dtype="int8"):
    return weight_bytes_by_part(cfg, weight_dtype)["total"]


def token_bytes(cfg, kv_dtype="bfloat16"):
    """One token's K and V in ONE layer (either kind: the kv heads are the
    same everywhere)."""
    s = _sizes(cfg)
    return 2 * s["KV"] * s["D"] * _DTYPE_BYTES[kv_dtype]


def ring_bytes_per_slot_layer(cfg, kv_dtype="bfloat16"):
    """One sequence's K ring and V ring in one sliding layer."""
    return _sizes(cfg)["W"] * token_bytes(cfg, kv_dtype)


def state_bytes_per_slot(cfg, kv_dtype="bfloat16"):
    """One sequence's rings, all sliding layers: what a decode slot holds
    whatever the sequence's length."""
    return _sizes(cfg)["window"] * ring_bytes_per_slot_layer(cfg, kv_dtype)


def page_bytes(cfg, page_size, kv_dtype="bfloat16"):
    """One page of the page pool over the FULL layers alone (a sliding layer
    holds no page)."""
    return _sizes(cfg)["full"] * page_size * token_bytes(cfg, kv_dtype)


def cache_bytes(cfg, slots, num_pages, page_size, kv_dtype="bfloat16"):
    """``(rings, pages)`` as allocated for ``slots`` decode slots."""
    return (slots * state_bytes_per_slot(cfg, kv_dtype),
            num_pages * page_bytes(cfg, page_size, kv_dtype))


def decode_step_bytes(cfg, rows, live_context_tokens, ring_tokens=None,
                      experts_touched=None, weight_dtype="int8",
                      kv_dtype="bfloat16"):
    """Least bytes one decode step of ``rows`` live sequences moves: every
    matrix once (the embedding table by ``rows`` rows; of the held experts
    those ``experts_touched`` a layer, all if None), the live tokens' K/V of
    the full layers, and ``ring_tokens`` ring rows a sliding layer (the sum
    over the rows of ``min(length, W)``; every row's whole ring if None)."""
    s = _sizes(cfg)
    p = weight_bytes_by_part(cfg, weight_dtype)
    experts = p["held_experts"]
    if experts_touched is not None:
        experts = experts * experts_touched / s["X"]
    if ring_tokens is None:
        ring_tokens = rows * s["W"]
    tb = token_bytes(cfg, kv_dtype)
    return (p["total"] - p["embedding"] - p["held_experts"] + experts
            + rows * s["E"] * _DTYPE_BYTES[weight_dtype]
            + live_context_tokens * s["full"] * tb
            + ring_tokens * s["window"] * tb)


def _attention_call(H, D, KV, queries_keys, queries, keys_read, kv_dtype,
                    act_dtype="bfloat16"):
    """``(operations, bytes)`` of one attention call: ``queries_keys`` (query,
    key) pairs under the masks at ``H`` heads (2 operations a multiply-add,
    scores and values), the keys' K and V read once, q in and o out."""
    ops = 2 * 2 * queries_keys * H * D
    bytes_ = (keys_read * 2 * KV * D * _DTYPE_BYTES[kv_dtype]
              + 2 * queries * H * D * _DTYPE_BYTES[act_dtype])
    return ops, bytes_


def window_decode_call(cfg, lengths, kv_dtype="bfloat16"):
    """``(operations, bytes)`` of ONE call (one sliding layer) of the window
    kernel over decode rows with ``lengths`` tokens behind them: a row reads
    ``min(length, W)`` ring rows (the kernel fetches the whole ring of a row
    with history: that is its cost, not the least) and its own token."""
    s = _sizes(cfg)
    seen = [min(n, s["W"] - 1) + 1 for n in lengths]
    read = sum(min(n, s["W"]) + 1 for n in lengths)
    return _attention_call(s["H_win"], s["D"], s["KV"], sum(seen),
                           len(lengths), read, kv_dtype)


def window_chunk_call(cfg, tokens, start, kv_dtype="bfloat16"):
    """``(operations, bytes)`` of ONE call (one sliding layer) of the window
    kernel over one row of ``tokens`` fresh tokens with ``start`` behind it:
    query ``i`` sees ``min(start + i, W - 1) + 1`` keys; the ring (``min(
    start, W)`` rows) and the fresh K/V are read once."""
    s = _sizes(cfg)
    pairs = sum(min(start + i, s["W"] - 1) + 1 for i in range(tokens))
    return _attention_call(s["H_win"], s["D"], s["KV"], pairs, tokens,
                           min(start, s["W"]) + tokens, kv_dtype)


def full_decode_call(cfg, lengths, kv_dtype="bfloat16"):
    """``(operations, bytes)`` of ONE call (one full layer) of the dense
    ragged paged kernel over decode rows with ``lengths`` tokens behind
    them."""
    s = _sizes(cfg)
    keys = sum(n + 1 for n in lengths)
    return _attention_call(s["H_full"], s["D"], s["KV"], keys, len(lengths),
                           keys, kv_dtype)


def full_chunk_call(cfg, tokens, start, kv_dtype="bfloat16"):
    """``(operations, bytes)`` of ONE call (one full layer) of the dense
    ragged paged kernel over one row of ``tokens`` fresh tokens with
    ``start`` behind it (causal: query ``i`` sees ``start + i + 1`` keys)."""
    s = _sizes(cfg)
    pairs = tokens * start + tokens * (tokens + 1) // 2
    return _attention_call(s["H_full"], s["D"], s["KV"], pairs, tokens,
                           start + tokens, kv_dtype)
