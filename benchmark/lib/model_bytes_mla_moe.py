"""Operations and bytes of a latent-attention (MLA), shared-and-routed-
experts decoder (DeepSeek-V2 family), from a configuration's sizes.

Everything is taken from the configuration's JSON file (Hugging Face key
names) and the serving settings beside it: nothing is read from the
program.  What a roofline share needs: the least bytes a decode step reads
given how many experts it touched, and the operations and bytes of one call
of each new kernel (the latent paged attention, the grouped expert product)
given its rows and contexts.
"""

_DTYPE_BYTES = {"int8": 1, "bfloat16": 2, "float32": 4}
ROPE_LANES = 128     # the rope key's lane-padded width in the page pool


def _matrix(rows, cols, wb):
    """Bytes of one matrix as served: int8 carries an f32 scale a column."""
    return rows * cols * wb + (cols * 4 if wb == 1 else 0)


def _sizes(cfg):
    return dict(
        E=cfg["hidden_size"], H=cfg["num_attention_heads"],
        R=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        F=cfg["intermediate_size"], Fx=cfg["moe_intermediate_size"],
        X=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
        shared=cfg.get("n_shared_experts") or 0, V=cfg["vocab_size"],
        L=cfg["num_hidden_layers"],
        dense=cfg.get("first_k_dense_replace", 0),
    )


def weight_bytes_by_part(cfg, weight_dtype="int8"):
    """Bytes of the weights as served, by part, over all layers held."""
    s = _sizes(cfg)
    wb = _DTYPE_BYTES[weight_dtype]
    E, H, L = s["E"], s["H"], s["L"]
    moe = L - s["dense"]
    attention = L * (
        _matrix(E, H * (s["dn"] + s["dr"]), wb)          # W_q
        + _matrix(E, s["R"] + s["dr"], wb)               # W_kva
        + _matrix(s["R"], H * (s["dn"] + s["dv"]), wb)   # W_kvb
        + _matrix(H * s["dv"], E, wb)                    # W_o
    )
    expert = 2 * _matrix(E, s["Fx"], wb) + _matrix(s["Fx"], E, wb)
    fs = s["shared"] * s["Fx"]
    parts = {
        "embedding": s["V"] * E * wb + (s["V"] * 4 if wb == 1 else 0),
        "head": 0 if cfg.get("tie_word_embeddings") else _matrix(
            E, s["V"], wb),
        "attention": attention,
        "dense_mlp": s["dense"] * (
            2 * _matrix(E, s["F"], wb) + _matrix(s["F"], E, wb)),
        "router": moe * _matrix(E, s["X"], wb),
        "routed_experts": moe * s["X"] * expert,
        "shared_experts": moe * (
            2 * _matrix(E, fs, wb) + _matrix(fs, E, wb)) if fs else 0,
        "norms": (L * (2 * E + s["R"]) + E) * 2,
    }
    parts["one_expert"] = expert
    parts["total"] = sum(v for k, v in parts.items() if k != "one_expert")
    return parts


def latent_bytes_per_token(cfg, kv_dtype="bfloat16", padded=True):
    """The cached latent and rope key of one token over all layers: as the
    pool holds and the kernel streams them (``padded``: the rope key in a
    128-lane slot), or the 512 + 64 values the algorithm needs."""
    s = _sizes(cfg)
    width = s["R"] + (ROPE_LANES if padded else s["dr"])
    return s["L"] * width * _DTYPE_BYTES[kv_dtype]


def page_bytes(cfg, page_size, kv_dtype="bfloat16"):
    return latent_bytes_per_token(cfg, kv_dtype) * page_size


def decode_step_bytes(cfg, live_context_tokens, experts_touched, rows,
                      weight_dtype="int8", kv_dtype="bfloat16",
                      padded=False):
    """Least bytes one decode step of ``rows`` sequences reads from HBM:
    every matrix outside the routed experts once (the embedding table by
    ``rows`` rows), ``experts_touched`` experts of each expert layer (the
    mean over the layers of the distinct experts the step routed to), and
    the latents of ``live_context_tokens`` tokens summed over the batch."""
    s = _sizes(cfg)
    p = weight_bytes_by_part(cfg, weight_dtype)
    wb = _DTYPE_BYTES[weight_dtype]
    fixed = (p["total"] - p["routed_experts"] - p["embedding"]
             + rows * s["E"] * wb)
    return (fixed
            + (s["L"] - s["dense"]) * experts_touched * p["one_expert"]
            + live_context_tokens * latent_bytes_per_token(
                cfg, kv_dtype, padded))


def mla_kernel_call(cfg, q_lens, contexts, kv_dtype="bfloat16",
                    act_dtype="bfloat16", padded=False):
    """``(operations, bytes)`` of ONE call (one layer) of the latent paged
    attention in its absorbed form.  ``q_lens[r]`` fresh tokens of row r,
    ``contexts[r]`` its history tokens in the page pool.  A fresh token
    attends the history and the fresh tokens up to itself.  Operations: for
    every (query, key) pair and head, a score over latent + rope widths and
    a value product over the latent width, 2 a multiply-add.  Bytes, the
    least: each row's history once, the queries in, the latents out."""
    s = _sizes(cfg)
    kb, ab = _DTYPE_BYTES[kv_dtype], _DTYPE_BYTES[act_dtype]
    key_w = s["R"] + (ROPE_LANES if padded else s["dr"])
    pairs = sum(q * c + q * (q + 1) / 2 for q, c in zip(q_lens, contexts))
    ops = 2 * s["H"] * pairs * (s["R"] + s["dr"] + s["R"])
    tokens = sum(q_lens)
    bytes_ = (sum(contexts) * key_w * kb                    # history
              + tokens * (s["R"] + s["dr"]) * ab            # fresh keys
              + tokens * s["H"] * (s["R"] + s["dr"]) * ab   # queries
              + tokens * s["H"] * s["R"] * ab)              # output
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


def roofline_share(ops, bytes_, seconds, peaks):
    """``(share in %, which bound)``: the least time the chip could take
    (operations over peak FLOP/s, bytes over peak bytes/s, the larger)
    over the measured time."""
    t_ops = ops / peaks["bf16_flops"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    bound = "hbm" if t_bytes >= t_ops else "flops"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
