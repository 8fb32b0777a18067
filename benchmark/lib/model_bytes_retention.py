"""Operations and bytes of a decoder whose token mixers are all POWER
RETENTION of degree 2 (Brumby family), from a configuration's sizes.

Everything is taken from the configuration's JSON file (Hugging Face key
names) and the ``serving`` settings beside it: nothing is read from the
program.  What a roofline share needs: the bytes of the weights and of one
sequence's state as served, the least bytes a decode step moves, and the
operations and bytes of one call (one layer) of the decode kernel and of the
512-token chunk form.

The state a kv head is ``S [D_held, D]`` float32, ``D_held`` the rows the
program HOLDS (``serving.retention_state_rows``: 8,704 at width 128, the
symmetric second power in 8 x 8 blocks, against ``D (D + 1) / 2`` = 8,256
distinct products), and the normaliser ``Z [D, D]`` float32.
"""

from benchmark.lib.model_bytes_mla_moe import (  # noqa: F401
    _DTYPE_BYTES, _matrix, roofline_share,
)


def _sizes(cfg):
    E, H = cfg["hidden_size"], cfg["num_attention_heads"]
    D = cfg.get("head_dim") or E // H
    return dict(
        E=E, H=H, KVH=cfg["num_key_value_heads"], D=D,
        F=cfg["intermediate_size"], V=cfg["vocab_size"],
        L=cfg["num_hidden_layers"],
        held=cfg["serving"]["retention_state_rows"],
    )


def parameter_count(cfg):
    """Parameters by part (the head is untied: counted beside the
    embedding)."""
    s = _sizes(cfg)
    E, HD, KD = s["E"], s["H"] * s["D"], s["KVH"] * s["D"]
    parts = {
        "retention_operators": s["L"] * (
            2 * E * HD + 2 * E * KD + E * s["KVH"] + s["KVH"] + 2 * s["D"]),
        "dense_mlp": s["L"] * 3 * E * s["F"],
        "embedding": s["V"] * E,
        "head": 0 if cfg.get("tie_word_embeddings") else s["V"] * E,
        "norms": s["L"] * 2 * E + E,
    }
    parts["total"] = sum(parts.values())
    return parts


def weight_bytes_by_part(cfg, weight_dtype="int8", act_dtype="bfloat16"):
    """Bytes of the weights as served, by part: a matrix at the weight dtype
    (int8 with an f32 scale a column; the embedding a scale a row), the gate
    projection a matrix like the others, its bias in f32, the norms in the
    activations' dtype."""
    s = _sizes(cfg)
    wb, ab = _DTYPE_BYTES[weight_dtype], _DTYPE_BYTES[act_dtype]
    E, HD, KD = s["E"], s["H"] * s["D"], s["KVH"] * s["D"]
    table = s["V"] * E * wb + (s["V"] * 4 if wb == 1 else 0)
    parts = {
        "retention_operators": s["L"] * (
            _matrix(E, HD, wb) + 2 * _matrix(E, KD, wb) + _matrix(HD, E, wb)
            + _matrix(E, s["KVH"], wb) + s["KVH"] * 4 + 2 * s["D"] * ab),
        "dense_mlp": s["L"] * (
            2 * _matrix(E, s["F"], wb) + _matrix(s["F"], E, wb)),
        "embedding": table,
        "head": 0 if cfg.get("tie_word_embeddings") else table,
        "norms": (s["L"] * 2 * E + E) * ab,
    }
    parts["total"] = sum(parts.values())
    return parts


def weight_bytes(cfg, weight_dtype="int8"):
    return weight_bytes_by_part(cfg, weight_dtype)["total"]


def state_bytes_per_slot_layer(cfg):
    """One sequence's state in one layer: ``S`` and ``Z`` of every kv head,
    float32."""
    s = _sizes(cfg)
    return s["KVH"] * (s["held"] + s["D"]) * s["D"] * 4


def state_bytes_per_slot(cfg):
    """One sequence's state, all layers: what a decode slot holds whatever
    the sequence's length."""
    return cfg["num_hidden_layers"] * state_bytes_per_slot_layer(cfg)


def kv_bytes_per_token(cfg, kv_dtype="bfloat16"):
    """No layer has pages: a token costs no byte of cache."""
    return 0


def decode_step_bytes(cfg, rows, weight_dtype="int8"):
    """Least bytes one decode step of ``rows`` live sequences moves: every
    matrix once (the embedding table by ``rows`` rows), and each row's state
    read once and written once in every layer."""
    s = _sizes(cfg)
    p = weight_bytes_by_part(cfg, weight_dtype)
    wb = _DTYPE_BYTES[weight_dtype]
    return (p["total"] - p["embedding"] + rows * s["E"] * wb
            + 2 * rows * state_bytes_per_slot(cfg))


def retention_decode_call(cfg, rows):
    """``(operations, bytes)`` of ONE call (one layer) of the decode kernel
    over ``rows`` live rows.  Bytes, the least: ``S`` read once and written
    once a row (the normaliser, q, k, v, the gate and the output are under
    2% of it and are counted).  Operations: a state entry is scaled, given
    one product and read by the group's H / KVH query heads: ``2 + 2 + 2 *
    H / KVH`` a held entry, all on the vector unit (no matrix product is
    involved: the operations' bound is far under the bytes')."""
    s = _sizes(cfg)
    entries = rows * s["KVH"] * s["held"] * s["D"]
    ops = entries * (4 + 2 * s["H"] // s["KVH"])
    small = rows * (s["KVH"] * 2 * s["D"] * s["D"]
                    + (2 * s["H"] + 3 * s["KVH"]) * s["D"]) * 4
    return ops, 2 * entries * 4 + small


def retention_chunk_call(cfg, tokens, prior_state=True):
    """``(operations, bytes)`` of ONE call (one layer) of the chunked form
    over one row of ``tokens`` fresh tokens.  Operations (2 a multiply-add):
    the scores and the weighted values inside the chunk (causal: half the
    pairs), ``phi(Q) S`` for what came before (``prior_state``), and ``phi(K)
    ^T V`` into the new state.  Bytes, the least: the state read once and
    written once, q, k, v in and y out; ``phi`` itself is never counted (the
    ``jax.numpy`` form does write ``phi(Q)`` and ``phi(K)`` through HBM: what
    that costs is in PERF.md)."""
    s = _sizes(cfg)
    T, H, KVH, D, held = tokens, s["H"], s["KVH"], s["D"], s["held"]
    pairs = T * (T + 1) / 2
    ops = 2 * H * pairs * 2 * D                    # scores, values
    ops += 2 * T * KVH * held * D                  # phi(K)^T V
    if prior_state:
        ops += 2 * T * H * held * D                # phi(Q) S
    state = KVH * (held + D) * D * 4
    bytes_ = (2 if prior_state else 1) * state + T * (
        2 * H + 2 * KVH) * D * 4
    return ops, bytes_
