"""Operations and bytes of a decoder of SLIDING-WINDOW attention layers (a
ring of the last ``W`` tokens' K/V a sequence) beside FULL-attention layers
(pages) at ONE count of query heads, with routed experts ALL held in every
layer, no shared expert and no dense layer (``mellum2-12b-a2.5b-int8``), from
a configuration's sizes.

``model_bytes_window_moe.py`` counts such a decoder from the configuration's
JSON keys already (a configuration without ``num_attention_heads_per_layer``,
``gating``, ``shared_expert_intermediate_size`` or ``published_num_experts``
reads as one head count, no gate, no shared expert and every expert held):
its functions are this module's, unchanged.  What is added is what a cell of
LONG sequences with EVERY expert held needs beside them: the experts a step's
rows touch, since the expert stream is nine tenths of the weights and a
decode step of a dozen rows leaves a fifth of it unread; and the pages the
dense paged kernel WALKS for a chunk, which are not the pages the chunk's
history holds: each 8-token query block of the row walks the history again.
Nothing is read from the program.
"""

from benchmark.lib.model_bytes_window_moe import (  # noqa: F401
    _sizes, cache_bytes, decode_step_bytes, full_chunk_call,
    full_decode_call, page_bytes, parameter_count, ring_bytes_per_slot_layer,
    roofline_share, state_bytes_per_slot, token_bytes, weight_bytes,
    weight_bytes_by_part, window_chunk_call, window_decode_call,
)

# tokens in a query block of the dense paged kernel and of the window kernel
# (``helix_tpu/ops/paged_kernel.py::query_block``): 1 for a decode call
CHUNK_QUERY_BLOCK = 8


def active_parameter_count(cfg):
    """Parameters a token's forward pass is said to use, as a model's name
    counts them ("A2.5B"): everything but the experts it does not choose,
    the embedding table among them (of which it reads one row)."""
    s, p = _sizes(cfg), parameter_count(cfg)
    return p["total"] - p["held_experts"] * (s["X"] - s["k"]) // s["X"]


def experts_touched(cfg, rows):
    """Expected distinct experts a layer's router gives ``rows`` tokens, each
    choosing ``k`` of ``X`` without replacement, under a router that spreads
    its choices evenly: ``X (1 - (1 - k / X) ** rows)``.  A seeded router
    over rows of unit RMS is near that; a trained one is more skewed and
    touches fewer."""
    s = _sizes(cfg)
    return s["X"] * (1.0 - (1.0 - s["k"] / s["X"]) ** rows)


def expert_rows(cfg, tokens):
    """Mean rows an expert sees of ``tokens`` tokens: ``tokens k / X``."""
    s = _sizes(cfg)
    return tokens * s["k"] / s["X"]


def decode_step_bytes_expected(cfg, lengths, weight_dtype="int8",
                               kv_dtype="bfloat16"):
    """Least bytes a decode step of rows with ``lengths`` tokens behind them
    moves at the expected count of experts touched: the weights outside the
    experts, that share of the experts, a ring's live rows a sliding layer
    and the live tokens' K/V a full layer."""
    s = _sizes(cfg)
    return decode_step_bytes(
        cfg, len(lengths), sum(lengths),
        ring_tokens=sum(min(n, s["W"]) for n in lengths),
        experts_touched=experts_touched(cfg, len(lengths)),
        weight_dtype=weight_dtype, kv_dtype=kv_dtype)


def pages_walked(lengths, page_size, tokens=1, block=1):
    """History pages ONE full layer's paged call fetches for rows with
    ``lengths`` tokens behind them and ``tokens`` fresh tokens each: a row's
    ``ceil(length / page)`` pages once a query block of ``block`` tokens
    (what ``helix_attn_page_bytes_read_total`` counts, a page's bytes
    each)."""
    blocks = -(-tokens // block)
    return sum(-(-n // page_size) * blocks for n in lengths)


def chunk_page_bytes_walked(cfg, tokens, start, page_size,
                            kv_dtype="bfloat16"):
    """K/V bytes the full layers' paged calls fetch for ONE chunk row of
    ``tokens`` fresh tokens over ``start`` tokens of history: every 8-token
    query block walks the whole history (at 512 over 8,192: 64 walks of 512
    pages a layer, 7.52 GB over seven layers, where the history itself is
    117 MB)."""
    return pages_walked([start], page_size, tokens,
                        CHUNK_QUERY_BLOCK) * page_bytes(
        cfg, page_size, kv_dtype)
