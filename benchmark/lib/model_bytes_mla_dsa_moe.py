"""Operations and bytes of a LATENT-attention decoder behind a learned
SPARSE-ATTENTION INDEXER (DeepSeek Sparse Attention, ``glm_moe_dsa``) whose
routed experts are HELD in part (one expert-parallel rank's), from a
configuration's sizes.

Everything is taken from the configuration's JSON file (Hugging Face key
names; ``n_routed_experts`` is what is loaded, ``published_n_routed_experts``
what the router scores) and the ``serving`` settings beside it: nothing is
read from the program.  What a roofline share needs: the bytes of the weights
by part, of a token and a page in each of the two pools, the least bytes a
decode step moves (beside the same step with every latent read), and the
operations and bytes of one call (one layer) of each new kernel:
``index_scores_call``, ``select_call``, ``sparse_mla_call``.
"""

from benchmark.lib.model_bytes_mla_moe import (  # noqa: F401
    ROPE_LANES, _DTYPE_BYTES, _matrix, roofline_share,
)


def _sizes(cfg):
    return dict(
        E=cfg["hidden_size"], H=cfg["num_attention_heads"],
        R=cfg["kv_lora_rank"], Rq=cfg["q_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], Hi=cfg["index_n_heads"],
        Di=cfg["index_head_dim"], K=cfg["index_topk"],
        F=cfg["intermediate_size"], Fx=cfg["moe_intermediate_size"],
        X=cfg["n_routed_experts"],
        X_all=cfg.get("published_n_routed_experts", cfg["n_routed_experts"]),
        shared=cfg.get("n_shared_experts") or 0, V=cfg["vocab_size"],
        L=cfg["num_hidden_layers"],
        dense=cfg.get("first_k_dense_replace", 0),
        top=cfg["num_experts_per_tok"],
    )


def parameter_count(cfg):
    """Parameters by part, over the layers and experts held (matrices and
    the small vectors beside them)."""
    s = _sizes(cfg)
    E = s["E"]
    qk = s["H"] * (s["dn"] + s["dr"])
    latent = (E * s["Rq"] + s["Rq"] * qk + E * (s["R"] + s["dr"])
              + s["R"] * s["H"] * (s["dn"] + s["dv"]) + s["H"] * s["dv"] * E)
    indexer = s["Rq"] * s["Hi"] * s["Di"] + E * s["Di"] + E * s["Hi"]
    moe_layers = s["L"] - s["dense"]
    parts = {
        "latent_mixers": s["L"] * latent,
        "indexers": s["L"] * indexer,
        "dense_mlp": s["dense"] * 3 * E * s["F"],
        "held_experts": moe_layers * s["X"] * 3 * E * s["Fx"],
        "shared_experts": moe_layers * 3 * E * s["shared"] * s["Fx"],
        "routers": moe_layers * E * s["X_all"],
        "embedding": s["V"] * E,
        "head": 0 if cfg.get("tie_word_embeddings") else s["V"] * E,
        # two norms a layer, the compressed query's and the latent's, the
        # index key's gain and bias, the selection bias, the final norm
        "vectors": (s["L"] * (2 * E + s["Rq"] + s["R"] + 2 * s["Di"])
                    + moe_layers * s["X_all"] + E),
    }
    parts["total"] = sum(parts.values())
    return parts


def weight_bytes_by_part(cfg, weight_dtype="int8", act_dtype="bfloat16"):
    """Bytes of the weights as served, by part: a matrix at the weight dtype
    (int8 with an f32 scale a column; the embedding a scale a row), norms in
    the activations' dtype, the selection bias in f32."""
    s = _sizes(cfg)
    wb, ab = _DTYPE_BYTES[weight_dtype], _DTYPE_BYTES[act_dtype]
    E = s["E"]
    m = lambda r, c: _matrix(r, c, wb)
    qk = s["H"] * (s["dn"] + s["dr"])
    hv = s["H"] * s["dv"]
    latent = (m(E, s["Rq"]) + m(s["Rq"], qk) + m(E, s["R"] + s["dr"])
              + m(s["R"], s["H"] * (s["dn"] + s["dv"])) + m(hv, E))
    indexer = (m(s["Rq"], s["Hi"] * s["Di"]) + m(E, s["Di"]) + m(E, s["Hi"]))
    expert = 2 * m(E, s["Fx"]) + m(s["Fx"], E)
    Fs = s["shared"] * s["Fx"]
    moe_layers = s["L"] - s["dense"]
    table = s["V"] * E * wb + (s["V"] * 4 if wb == 1 else 0)
    parts = {
        "latent_mixers": s["L"] * latent,
        "indexers": s["L"] * indexer,
        "dense_mlp": s["dense"] * (2 * m(E, s["F"]) + m(s["F"], E)),
        "held_experts": moe_layers * s["X"] * expert,
        "shared_experts": moe_layers * (2 * m(E, Fs) + m(Fs, E)),
        "routers": moe_layers * (m(E, s["X_all"]) + s["X_all"] * 4),
        "embedding": table,
        "head": 0 if cfg.get("tie_word_embeddings") else table,
        "vectors": (s["L"] * (2 * E + s["Rq"] + s["R"] + 2 * s["Di"])
                    + E) * ab,
    }
    parts["total"] = sum(parts.values())
    return parts


def weight_bytes(cfg, weight_dtype="int8"):
    return weight_bytes_by_part(cfg, weight_dtype)["total"]


def latent_bytes_per_token_layer(cfg, kv_dtype="bfloat16"):
    """A token's row in the latent pool, as allocated: the latent and the
    rope key in a 128-lane slot."""
    s = _sizes(cfg)
    return (s["R"] + ROPE_LANES) * _DTYPE_BYTES[kv_dtype]


def index_key_bytes_per_token_layer(cfg, kv_dtype="bfloat16"):
    """A token's index key in the index-key pool."""
    return _sizes(cfg)["Di"] * _DTYPE_BYTES[kv_dtype]


def token_bytes(cfg, kv_dtype="bfloat16"):
    """``(latent pool, index-key pool)`` bytes a token over all layers."""
    L = _sizes(cfg)["L"]
    return (L * latent_bytes_per_token_layer(cfg, kv_dtype),
            L * index_key_bytes_per_token_layer(cfg, kv_dtype))


def page_bytes(cfg, page_size, kv_dtype="bfloat16"):
    """One page of both pools over the layers, as allocated."""
    return page_size * sum(token_bytes(cfg, kv_dtype))


def decode_step_bytes(cfg, contexts, experts_touched=None,
                      weight_dtype="int8", kv_dtype="bfloat16",
                      every_latent=False):
    """Least bytes one decode step moves for rows whose contexts (keys a
    row, its own among them) are ``contexts``: every matrix once (the
    embedding table by a row a sequence; of the held experts those
    ``experts_touched`` a layer, all if None), each row's index keys (``Di``
    values a key and layer) and the latent rows of the ``min(n, index_topk)``
    keys it attends.  ``every_latent``: the same step with every latent read
    and no index key (what latent attention without the indexer moves)."""
    s = _sizes(cfg)
    p = weight_bytes_by_part(cfg, weight_dtype)
    wb = _DTYPE_BYTES[weight_dtype]
    experts = p["held_experts"]
    if experts_touched is not None:
        experts = experts * experts_touched / s["X"]
    lat, key = token_bytes(cfg, kv_dtype)
    if every_latent:
        cache = sum(contexts) * lat
    else:
        cache = sum(n * key + min(n, s["K"]) * lat for n in contexts)
    return (p["total"] - p["embedding"] - p["held_experts"] + experts
            + len(contexts) * s["E"] * wb + cache)


def index_scores_call(cfg, queries, keys, rows=1, kv_dtype="bfloat16"):
    """``(operations, bytes)`` of ONE call (one layer) of the scoring kernel
    ``dsa_index_scores_tpu``: ``rows`` rows of ``queries`` queries each
    against ``keys`` index keys each.  Operations: the products ``2 Hi Di`` a
    (query, key), then a ReLU, a weight and an add a head (``3 Hi``, on the
    vector unit).  Bytes, the least: the keys and the queries read once,
    the float32 scores written once."""
    s = _sizes(cfg)
    b = _DTYPE_BYTES[kv_dtype]
    pairs = rows * queries * keys
    ops = pairs * s["Hi"] * (2 * s["Di"] + 3)
    bytes_ = (rows * keys * s["Di"] * b
              + rows * queries * s["Hi"] * (s["Di"] * b + 4) + pairs * 4)
    return ops, bytes_


def select_call(cfg, queries, keys, rows=1):
    """``(operations, bytes)`` of the choice a layer: a decode row's top-k
    is XLA's (not counted here: ``lax.top_k``); a chunk's threshold is 32
    passes of compare-and-count over the float32 scores, then one pass that
    writes the float32 bias."""
    pairs = rows * queries * keys
    return 32 * 2 * pairs + pairs, 32 * pairs * 4 + 2 * pairs * 4


def sparse_mla_call(cfg, queries, keys, rows=1, kv_dtype="bfloat16"):
    """``(operations, bytes)`` of ONE call (one layer) of the sparse latent
    attention kernel ``mla_sparse_attention_tpu``: ``rows`` rows of
    ``queries`` queries' H heads over ``keys`` gathered latent rows each
    (a decode row: 1 query over its ``index_topk`` chosen rows; a chunk: its
    tokens over the dense copy of the history, what a query dropped masked:
    the products are dense).  Operations: scores over the whole row (``R +
    128`` lanes) and values over the latent, 2 a multiply-add.  Bytes, the
    least: the rows and the queries read once, the bias, the output."""
    s = _sizes(cfg)
    b = _DTYPE_BYTES[kv_dtype]
    W = s["R"] + ROPE_LANES
    pairs = rows * queries * keys
    ops = 2 * pairs * s["H"] * (W + s["R"])
    bytes_ = (rows * keys * W * b + rows * queries * s["H"] * (W + s["R"]) * b
              + pairs * 4)
    return ops, bytes_
