"""The plain reference of ``mellum2-12b-a2.5b-int8``: a decoder of
SLIDING-WINDOW attention layers beside FULL-attention layers with ONE count of
query heads, the whole head rotated in both kinds at one theta (YaRN on the
full layers alone), no gate, and in EVERY layer routed experts behind a
softmax router renormalised over the chosen, no shared expert and no dense
layer: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``.  Whole-sequence attention with
the explicit causal and window masks, the experts as a loop.  No ring, no
page, no cache, no batching, no kernel, no quantisation.

Equations.  ``n(x; w) = x / rms(x) * w``, eps rms_norm_eps.  E hidden, D = 128
the head width, H query heads over KV kv heads in every layer, W =
sliding_window, n routed experts of which each token takes k.
  h0 = Emb[tokens]
  a block:  x' = n(h; w_a);  h = h + Attn(x');  x'' = n(h; w_m);  h = h + FFN(x'')
  Attn:  q = x' W_q -> [S, H, D];  k = x' W_k, v = x' W_v -> [S, KV, D]; no
      bias, no q/k norm
    rope on all D dims, pairs (i, i + D/2), in both kinds at theta 500,000:
    a SLIDING layer (``sliding_attention``) at the plain inverse frequencies,
    a FULL layer (``full_attention``) at YaRN's blend (factor 16 over 8,192,
      beta 32 / 1) with cos and sin times attention_factor
    score_h[i, j] = q_h[i] . k_{h // (H / KV)}[j] / sqrt(D), kept where
      j <= i, and on a sliding layer where also i - j < W (W keys, the query's
      own among them)
    o_h = softmax(score_h) v;  Attn = concat_h(o_h) W_o
  FFN:  p = softmax(x'' W_r) over ALL n experts;  idx = top-k(p);
    w = p[idx] / sum p[idx]  (norm_topk_prob true; p[idx] as it is if false)
    FFN = sum_j w_j Expert_idx_j(x''),  Expert(x) = W_d(silu(x W_gate) * (x W_up))
  logits = n(h; w_f) W_head      (untied)

What it takes from ``reference_window_moe_decoder.py`` UNCHANGED, because
those functions compute exactly the lines above: ``norm``, ``glu`` (a SwiGLU
from the program's tree), ``rope_table`` (a kind's inverse frequencies and
cos/sin factor from ``rope_parameters``), ``rope`` (the rotation) and
``kinds`` / ``layer_homes`` (where the program's tree keeps a layer).  The
attention, the expert layer, the block and ``forward`` are this file's.

Departures from the published description, and ASSUMED readings of the
published config (each also in the configuration file's ``assumed``; the
config states the sizes and none of these):
- no q/k norm: no key of the config names one.  ``max_window_layers`` and
  ``use_sliding_window`` are keys of a lineage whose later members norm q and
  k a head with no key that says so: this is the reading taken, and
  ``qk_norm: true`` is one field of the program's configuration away;
- ``max_window_layers`` 0 is read by no layer: ``layer_types`` names every
  layer's kind;
- YaRN's ``attention_factor`` multiplies cos and sin (Hugging Face's
  convention for a stated factor); the scores take ``D ** -0.5`` and no
  further factor;
- the window counts the query itself: ``sliding_window`` 1,024 keys at most;
- the router's weight multiplies an expert's OUTPUT, and carries no scale;
- the multi-token-prediction head that the family's description names has no
  key in the config: its equations cannot be written down and it is not
  built;
- weights are the PROGRAM's parameter tree (``models/llama.py::init_params``),
  read as float32, an int8 leaf times its scale: the served quantisation is
  shared by both sides, so a comparison shows the program's bf16 activations,
  its rings and pages, its kernels and its split of the token axis, and not
  the quantisation;
- attention scores are computed in BLOCKS of queries (``block`` rows at a
  time, every key at once), so that eight thousand tokens at 32 heads fit;
- ``forward(..., layers=(lo, hi), h=...)`` runs a block of layers from a
  hidden state (the blocks chained give the full forward); ``rows`` picks the
  positions whose logits come back;
- the faults a tolerance must catch, each off by default: ``no_window`` (a
  sliding layer attends every earlier token), ``window=n`` (a window of ``n``
  keys, not ``sliding_window``), ``window_off_by_one`` (W + 1 keys),
  ``yarn_on_sliding`` (the sliding layers given the full layers' table and
  factor), ``plain_on_full`` (the full layers given the sliding layers'
  plain table), ``drop_attention_factor`` (YaRN's table with cos and sin as
  they are), ``no_renorm`` (the chosen probabilities as they are),
  ``drop_expert=e`` (expert ``e``'s part left out of every layer's sum;
  ``"all"``: every expert's) and ``ring_8bit`` (a sliding layer's K and V
  rounded to 8-bit floats, 4 exponent and 3 mantissa bits: what a ring one
  precision under the configuration's bfloat16 would hold).
"""

import jax
import jax.numpy as jnp

from benchmark.lib.reference_hybrid_conv_moe_decoder import (  # noqa: F401
    layer_homes,
)
from benchmark.lib.reference_window_moe_decoder import (  # noqa: F401
    _f32, glu, kinds, norm, rope, rope_table,
)

FULL, SLIDING = "full_attention", "sliding_attention"


def attention(x, lp, i, cfg, pos, kind, faults, block):
    """One attention layer over one whole sequence ``x [S, E]``, ``kind`` a
    key of ``rope_parameters``."""
    S = x.shape[0]
    D, KV = cfg["head_dim"], cfg["num_key_value_heads"]
    H = cfg["num_attention_heads"]
    q = (x @ _f32(lp["wq"], i)).reshape(S, H, D)
    k = (x @ _f32(lp["wk"], i)).reshape(S, KV, D)
    v = (x @ _f32(lp["wv"], i)).reshape(S, KV, D)
    sliding = kind == SLIDING
    table = kind
    if sliding and faults.get("yarn_on_sliding"):
        table = FULL
    if not sliding and faults.get("plain_on_full"):
        table = SLIDING
    inv, factor = rope_table(cfg, table)
    if faults.get("drop_attention_factor"):
        factor = 1.0
    q, k = rope(q, pos, inv, factor), rope(k, pos, inv, factor)
    if sliding and faults.get("ring_8bit"):
        k, v = (jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)
                for a in (k, v))
    k, v = (jnp.repeat(a, H // KV, axis=1) for a in (k, v))
    W = None
    if sliding and not faults.get("no_window"):
        W = faults.get("window") or cfg["sliding_window"]
        W += bool(faults.get("window_off_by_one"))
    out = []
    for lo in range(0, S, block):                  # blocks of queries
        s = jnp.einsum("qhd,khd->hqk", q[lo:lo + block], k) * D ** -0.5
        i_, j_ = pos[lo:lo + block, None], pos[None, :]
        keep = j_ <= i_
        if W is not None:
            keep = keep & (i_ - j_ < W)
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v))
    o = jnp.concatenate(out, axis=0)                              # [S, H, D]
    return o.reshape(S, H * D) @ _f32(lp["wo"], i)


def expert_layer(x, lp, i, cfg, faults):
    """The softmax router over all the experts, the top k by probability
    renormalised over themselves, and the sum over the chosen."""
    p = jax.nn.softmax(x @ _f32(lp["router"], i), axis=-1)         # [S, n]
    w, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True) and not faults.get("no_renorm"):
        w = w / jnp.sum(w, -1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(cfg["num_experts"]):            # a loop over the experts
        if faults.get("drop_expert") in (e, "all"):
            continue
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)        # [S]
        out = out + w_e[:, None] * glu(x, lp["experts"], i, e)
    return out


def layer(h, lp, i, cfg, pos, kind, dense, faults, block=256):
    """One block: ``lp`` the stack that holds it, ``i`` its index there,
    ``kind`` its attention's (a key of ``rope_parameters``), ``dense`` a
    dense SwiGLU of ``intermediate_size`` (a leading ``mlp_layer_types``
    dense layer; the published model has none)."""
    eps = cfg["rms_norm_eps"]
    x = norm(h, _f32(lp["attn_norm"], i), eps)
    h = h + attention(x, lp, i, cfg, pos, kind, faults, block)
    x = norm(h, _f32(lp["mlp_norm"], i), eps)
    return h + (glu(x, lp, i) if dense else expert_layer(
        x, lp, i, cfg, faults))


def forward(params, cfg, tokens, rows=None, layers=None, h=None, head=True,
            block=256, **faults):
    """Logits ``[S, vocab]`` (``[len(rows), vocab]`` with ``rows``) of one
    sequence ``tokens [S]``; ``cfg`` has the Hugging Face keys of the
    configuration's JSON file.

    ``layers=(lo, hi)`` runs layers lo..hi-1 only: from the embedding if
    ``h`` is None, else from the hidden state ``h [S, E]``; ``head=False``
    returns the hidden state instead of logits (for the next block)."""
    view = kinds(cfg)
    homes = layer_homes(view)
    lo, hi = layers or (0, cfg["num_hidden_layers"])
    pos = jnp.arange(tokens.shape[0])
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        if h is None:
            emb = params["embed"]
            h = jnp.asarray(emb["weight"], jnp.float32)[tokens]
            if "embed_scale" in emb:
                h = h * jnp.asarray(emb["embed_scale"], jnp.float32)[tokens]
        for l in range(lo, hi):
            key, i = homes[l]
            h = layer(h, params[key], i, cfg, pos, cfg["layer_types"][l],
                      l < view["num_dense_layers"], faults, block)
        if not head:
            return h
        if rows is not None:
            h = h[jnp.asarray(rows)]
        h = norm(h, jnp.asarray(params["final_norm"]["weight"], jnp.float32),
                 eps)
        head_p = params["lm_head"]
        w = jnp.asarray(head_p["weight"], jnp.float32)
        if "scale" in head_p:
            w = w * jnp.asarray(head_p["scale"], jnp.float32)
        return h @ w
