"""Bytes a decode step has to read, from a configuration's sizes.

A decode step of a dense decoder reads every weight once (int8: one byte a
weight plus a per-output-channel scale) and the K and V of every live
context token.  These functions take the sizes from the configuration's
JSON file (Hugging Face key names) and the serving settings beside them
(``serving``): nothing is read from the program.
"""

_DTYPE_BYTES = {"int8": 1, "bfloat16": 2, "float32": 4}


def weight_bytes(cfg, weight_dtype="int8"):
    """Bytes of the weights as served.  Matrices (attention and FFN
    projections, embedding, LM head) at ``weight_dtype`` with one f32 scale
    per output channel when int8; norms and biases in bf16."""
    h = cfg["hidden_size"]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // heads
    ffn, vocab, layers = (cfg["intermediate_size"], cfg["vocab_size"],
                          cfg["num_hidden_layers"])
    wb = _DTYPE_BYTES[weight_dtype]
    # (rows, output channels) of each matrix of a layer
    mats = [(h, heads * d), (h, kvh * d), (h, kvh * d), (heads * d, h),
            (h, ffn), (h, ffn), (ffn, h)]
    per_layer = sum(r * c * wb + (c * 4 if wb == 1 else 0) for r, c in mats)
    per_layer += 2 * h * 2                                   # two RMSNorms
    if cfg.get("attention_bias") or cfg.get("model_type") == "qwen2":
        per_layer += (heads * d + 2 * kvh * d) * 2           # qkv bias
    embed = vocab * h * wb + (vocab * 4 if wb == 1 else 0)
    head = 0 if cfg.get("tie_word_embeddings") else (
        vocab * h * wb + (vocab * 4 if wb == 1 else 0))
    return layers * per_layer + embed + head + h * 2         # + final norm


def kv_bytes_per_token(cfg, kv_dtype="bfloat16"):
    """K and V of one token over all layers."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d = cfg.get("head_dim") or h // heads
    return (cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * d * 2
            * _DTYPE_BYTES[kv_dtype])


def page_bytes(cfg, page_size, kv_dtype="bfloat16"):
    return kv_bytes_per_token(cfg, kv_dtype) * page_size


def decode_step_bytes(cfg, live_context_tokens, weight_dtype="int8",
                      kv_dtype="bfloat16", embed_rows=0):
    """Least bytes one decode step reads from HBM: all matrices but the
    embedding table (a decode step gathers ``embed_rows`` rows of it, one a
    sequence), plus the K and V of ``live_context_tokens`` tokens summed
    over the batch."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    wb = _DTYPE_BYTES[weight_dtype]
    embed = vocab * h * wb + (vocab * 4 if wb == 1 else 0)
    if cfg.get("tie_word_embeddings"):
        embed = 0          # the table is read whole as the LM head
    return (weight_bytes(cfg, weight_dtype) - embed + embed_rows * h * wb
            + live_context_tokens * kv_bytes_per_token(cfg, kv_dtype))
