"""Operations and bytes of a hybrid decoder of gated DELTA-RULE layers beside
gated GQA-attention layers (pages), with routed experts in EVERY layer of
which one expert-parallel rank's are HELD, and a gated shared expert, from a
configuration's sizes.

Everything is taken from the configuration's JSON file (Hugging Face key
names; ``num_experts`` is what is loaded, ``published_num_experts`` what the
router scores) and the ``serving`` settings beside it: nothing is read from
the program.  What a roofline share needs: the bytes of the weights, of a
token's pages and of one sequence's state as served, the least bytes a decode
step and a chunk step move, and the operations and bytes of one call (one
layer) of each kernel: the delta-rule decode kernel, its chunked form, the
paged kernel over rows of given lengths, the grouped expert product over the
experts a step touches.

A delta layer's state a slot: ``S [value heads, dk, dv]`` float32 and the
conv tail ``[K - 1, 2 * key heads * dk + value heads * dv]`` in the
activations' dtype.
"""

from benchmark.lib.model_bytes_mla_moe import (  # noqa: F401
    _DTYPE_BYTES, _matrix, roofline_share,
)

CHUNK = 64      # tokens the chunked form takes at a time


def _sizes(cfg):
    L = cfg["num_hidden_layers"]
    every = cfg.get("full_attention_interval", 4)
    attn = len([l for l in range(L) if (l + 1) % every == 0])
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    Fx = cfg["moe_intermediate_size"]
    return dict(
        E=cfg["hidden_size"], H=cfg["num_attention_heads"],
        KV=cfg["num_key_value_heads"], D=cfg["head_dim"], Fx=Fx,
        X=cfg["num_experts"],
        X_all=cfg.get("published_num_experts", cfg["num_experts"]),
        k=cfg["num_experts_per_tok"],
        Fs=cfg.get("shared_expert_intermediate_size") or 0,
        V=cfg["vocab_size"], L=L, attn=attn, delta=L - attn,
        nk=nk, nv=nv, dk=dk, dv=dv, K=cfg["linear_conv_kernel_dim"],
        C=2 * nk * dk + nv * dv,
    )


def parameter_count(cfg):
    """Parameters by part, over the layers and experts held (matrices and
    the small vectors beside them)."""
    s = _sizes(cfg)
    E, hd = s["E"], s["H"] * s["D"]
    delta = (E * s["C"] + 2 * E * s["nv"] * s["dv"] + 2 * E * s["nv"]
             + s["C"] * s["K"] + 2 * s["nv"] + s["dv"])
    attn = 2 * E * hd + 2 * E * s["KV"] * s["D"] + hd * E + 2 * s["D"]
    parts = {
        "delta_mixers": s["delta"] * delta,
        "attention_mixers": s["attn"] * attn,
        "held_experts": s["L"] * s["X"] * 3 * E * s["Fx"],
        "shared_experts": s["L"] * (3 * E * s["Fs"] + (E if s["Fs"] else 0)),
        "routers": s["L"] * E * s["X_all"],
        "embedding": s["V"] * E,
        "head": 0 if cfg.get("tie_word_embeddings") else s["V"] * E,
        "norms": s["L"] * 2 * E + E,
    }
    parts["total"] = sum(parts.values())
    return parts


def weight_bytes_by_part(cfg, weight_dtype="int8", act_dtype="bfloat16"):
    """Bytes of the weights as served, by part: a matrix at the weight dtype
    (int8 with an f32 scale a column; the embedding a scale a row), norms and
    conv taps in the activations' dtype, ``A_log`` and ``dt_bias`` in f32."""
    s = _sizes(cfg)
    wb, ab = _DTYPE_BYTES[weight_dtype], _DTYPE_BYTES[act_dtype]
    E, hd = s["E"], s["H"] * s["D"]
    m = lambda r, c: _matrix(r, c, wb)
    delta = (m(E, s["C"]) + 2 * m(E, s["nv"]) + m(E, s["nv"] * s["dv"])
             + m(s["nv"] * s["dv"], E) + s["C"] * s["K"] * ab
             + 2 * s["nv"] * 4 + s["dv"] * ab)
    attn = (2 * m(E, hd) + 2 * m(E, s["KV"] * s["D"]) + m(hd, E)
            + 2 * s["D"] * ab)
    expert = 2 * m(E, s["Fx"]) + m(s["Fx"], E)
    shared = (2 * m(E, s["Fs"]) + m(s["Fs"], E) + m(E, 1)) if s["Fs"] else 0
    table = s["V"] * E * wb + (s["V"] * 4 if wb == 1 else 0)
    parts = {
        "delta_mixers": s["delta"] * delta,
        "attention_mixers": s["attn"] * attn,
        "held_experts": s["L"] * s["X"] * expert,
        "shared_experts": s["L"] * shared,
        "routers": s["L"] * m(E, s["X_all"]),
        "embedding": table,
        "head": 0 if cfg.get("tie_word_embeddings") else table,
        "norms": (s["L"] * 2 * E + E) * ab,
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


def kv_bytes_per_token(cfg, kv_dtype="bfloat16"):
    """K and V of one token over the attention layers."""
    s = _sizes(cfg)
    return s["attn"] * 2 * s["KV"] * s["D"] * _DTYPE_BYTES[kv_dtype]


def page_bytes(cfg, page_size, kv_dtype="bfloat16"):
    return page_size * kv_bytes_per_token(cfg, kv_dtype)


def experts_touched(cfg, rows):
    """Held experts some token of ``rows`` reaches in ONE layer, under an
    even router: ``X (1 - (1 - 1 / X_all) ** (k rows))``."""
    s = _sizes(cfg)
    return s["X"] * (1.0 - (1.0 - 1.0 / s["X_all"]) ** (s["k"] * rows))


def held_rows(cfg, rows):
    """(token, choice) assignments of ``rows`` tokens that land on this
    rank's experts in one layer, under an even router."""
    s = _sizes(cfg)
    return rows * s["k"] * s["X"] / s["X_all"]


def _step_weight_bytes(cfg, rows, weight_dtype):
    """Every matrix once: the embedding table by ``rows`` rows, of the held
    experts those an even router's ``rows`` tokens touch."""
    s = _sizes(cfg)
    p = weight_bytes_by_part(cfg, weight_dtype)
    experts = p["held_experts"] * experts_touched(cfg, rows) / s["X"]
    return (p["total"] - p["embedding"] - p["held_experts"] + experts
            + rows * s["E"] * _DTYPE_BYTES[weight_dtype])


def decode_step_bytes(cfg, rows, live_context_tokens, weight_dtype="int8",
                      kv_dtype="bfloat16"):
    """Least bytes one decode step of ``rows`` live sequences moves: every
    matrix once (the held experts the rows touch), each row's delta state
    read once and written once, and the live tokens' K and V read once."""
    return (_step_weight_bytes(cfg, rows, weight_dtype)
            + 2 * rows * state_bytes_per_slot(cfg)
            + live_context_tokens * kv_bytes_per_token(cfg, kv_dtype))


def decode_step_ops(cfg, rows, live_context_tokens):
    """Operations of one decode step (2 a multiply-add): the matrices a row
    meets (its ten experts' share held here), attention over its context,
    the delta rule's 7 an entry."""
    s = _sizes(cfg)
    p = parameter_count(cfg)
    active = (p["total"] - p["embedding"] - p["held_experts"]
              + s["L"] * held_rows(cfg, 1) * 3 * s["E"] * s["Fx"])
    attn = 4 * live_context_tokens * s["attn"] * s["H"] * s["D"]
    return (2 * rows * active + attn
            + s["delta"] * deltanet_decode_call(cfg, rows)[0])


def chunk_step(cfg, tokens, history, rows=0, live_context_tokens=0,
               weight_dtype="int8", kv_dtype="bfloat16"):
    """``(operations, bytes)`` of one step that carries ONE chunk row of
    ``tokens`` fresh tokens behind ``history`` cached ones, beside ``rows``
    decode rows over ``live_context_tokens``: every matrix once (nearly all
    the held experts: the step's tokens touch them), the chunk's slot state
    read and written once and its history's K and V read once a query block
    (``paged_kernel_call``), the decode rows as in ``decode_step_bytes``."""
    s = _sizes(cfg)
    T = tokens + rows
    p = parameter_count(cfg)
    active = (p["total"] - p["embedding"] - p["held_experts"]
              + s["L"] * held_rows(cfg, 1) * 3 * s["E"] * s["Fx"])
    # the head reads the rows that sample: the chunk's last and the decode
    # rows
    head = p["head"] or p["embedding"]
    ops = 2 * T * (active - head) + 2 * (rows + 1) * head
    a_ops, a_bytes = paged_kernel_call(
        cfg, [tokens] + [1] * rows,
        [history] + [live_context_tokens // max(rows, 1)] * rows, kv_dtype)
    d_ops = deltanet_chunk_call(cfg, tokens)[0] + (
        deltanet_decode_call(cfg, rows)[0] if rows else 0)
    ops += s["attn"] * a_ops + s["delta"] * d_ops
    bytes_ = (_step_weight_bytes(cfg, T, weight_dtype)
              + 2 * (rows + 1) * state_bytes_per_slot(cfg)
              + s["attn"] * a_bytes)
    return ops, bytes_


# tokens in a chunk row's query block at a group of 8 or under
# (``ops/paged_kernel.py::chunk_query_block``): a block walks the row's
# history once
CHUNK_QUERY_BLOCK = 128


def paged_kernel_call(cfg, q_lens, contexts, kv_dtype="bfloat16"):
    """``(operations, bytes)`` of ONE call (one layer) of the paged kernel
    over rows of ``q_lens`` fresh tokens behind ``contexts`` cached ones.
    Operations: ``q k`` and ``p v`` over the keys a query sees (its history
    and the fresh keys up to itself), every query head.  Bytes, the least as
    the kernel walks: a row's history K and V once a query block of its own
    (a decode row once; a 512-token chunk four times), its fresh K and V
    once, q in and o out."""
    s = _sizes(cfg)
    kvb = _DTYPE_BYTES[kv_dtype]
    ops = bytes_ = 0
    for n, ctx in zip(q_lens, contexts):
        seen = n * ctx + n * (n + 1) // 2
        ops += 4 * seen * s["H"] * s["D"]
        blocks = -(-n // CHUNK_QUERY_BLOCK)
        bytes_ += 2 * (blocks * ctx + n) * s["KV"] * s["D"] * kvb
        bytes_ += 2 * n * s["H"] * s["D"] * kvb
    return ops, bytes_


def grouped_expert_product(cfg, tokens, weight_dtype="int8",
                           act_dtype="bfloat16"):
    """``(operations, bytes)`` of ONE layer's two grouped calls (gate and up
    in one, down in the other) over the assignments ``tokens`` tokens leave
    on this rank under an even router: the products of the rows routed, the
    weights of the experts touched once, the rows in and out."""
    s = _sizes(cfg)
    wb, ab = _DTYPE_BYTES[weight_dtype], _DTYPE_BYTES[act_dtype]
    routed = held_rows(cfg, tokens)
    touched = experts_touched(cfg, tokens)
    ops = 2 * routed * 3 * s["E"] * s["Fx"]
    expert = 2 * _matrix(s["E"], s["Fx"], wb) + _matrix(s["Fx"], s["E"], wb)
    bytes_ = touched * expert + routed * (
        2 * s["E"] + 2 * s["Fx"]) * ab
    return ops, bytes_


def deltanet_decode_call(cfg, rows):
    """``(operations, bytes)`` of ONE call (one layer) of the decode kernel
    over ``rows`` live rows (``model_bytes_deltanet_mla_moe``'s count at this
    configuration's heads).  Bytes, the least: ``S`` read once and written
    once a row, and the five vectors a head in and one out.  Operations: a
    state entry is decayed, read under ``k``, written and read under ``q``:
    ``1 + 2 + 2 + 2`` an entry, all on the vector unit."""
    s = _sizes(cfg)
    entries = rows * s["nv"] * s["dk"] * s["dv"]
    return 7 * entries, 2 * entries * 4 + rows * s["nv"] * 6 * s["dv"] * 4


def deltanet_chunk_call(cfg, tokens):
    """``(operations, bytes)`` of ONE call (one layer) of the chunked form
    over one row of ``tokens`` fresh tokens, 64 at a time (2 operations a
    multiply-add; ``model_bytes_deltanet_mla_moe``'s count at this
    configuration's heads).  A chunk of ``C`` tokens a value head: ``k k^T``
    and ``q k^T`` (``2 C^2 dk``), the unit lower triangular solve against
    ``dv + dk`` columns (``C^2 (dv + dk) / 2``), the writes less what the
    state held (``C dk dv``), the state's and the chunk's share of the output
    (``C dk dv + C^2 dv``) and the new state (``C dk dv``).  Bytes, the
    least: the state read once and written once, q, k, v, g and beta in and
    o out."""
    s = _sizes(cfg)
    C, dk, dv = CHUNK, s["dk"], s["dv"]
    chunks = -(-tokens // C)
    mults = (2 * C * C * dk + C * C * (dv + dk) // 2 + 3 * C * dk * dv
             + C * C * dv)
    ops = 2 * mults * chunks * s["nv"]
    bytes_ = (2 * s["nv"] * dk * dv * 4
              + tokens * s["nv"] * (2 * dk + 2 * dv + 2) * 4)
    return ops, bytes_
