"""The plain reference of ``glm-5-int8``: GLM-5's (``model_type:
glm_moe_dsa``) published forward pass in straightforward ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``: latent attention
decompressed (every head's K and V built from the latent), the sparse-
attention indexer scored densely and its choice a sort, a loop over experts;
no cache, kernel, batching or quantisation.

Equations (``eps`` = rms_norm_eps; H heads; dn, dr, dv = qk_nope, qk_rope, v
head widths; R = kv_lora_rank; Hi, Di = index_n_heads, index_head_dim; K =
index_topk), x = RMSNorm(h) the layer's normed input, positions t, s:
  c^Q = RMSNorm(x W_qa);  q = c^Q W_qb -> [S, H, dn + dr] = q_nope | q_pe
  [c^KV | k^R] = x W_kva; c = RMSNorm(c^KV); kv = c W_kvb -> k_nope | v
  q_pe, k_pe = RoPE(q_pe), RoPE(k^R): pairs (2i, 2i+1), theta rope_theta
  score_h[t, s] = (dn + dr)^-0.5 (q_nope_h[t].k_nope_h[s] + q_pe_h[t].k_pe[s])
  indexer (DeepSeek Sparse Attention: the DeepSeek-V3.2-Exp report's
  equations 1-2, ``Indexer`` of its inference/model.py):
    q^I = c^Q W^I_qb -> [S, Hi, Di];  k^I = LayerNorm(x W^I_k) (gain, bias,
    eps 1e-6), ONE key a token; RoPE over the FIRST dr dims of each (pairs
    (2i, 2i+1): indexer_rope_interleave true), the rest pass;
    w = x W^I_w -> [S, Hi]
    I[t, s] = Hi^-0.5 Di^-0.5 sum_j w[t, j] relu(q^I[t, j] . k^I[s]),  s <= t
  S_t = every s <= t while t + 1 <= K, else the K positions of largest
    I[t, s], ties to the smaller s
  a_h[t] = sum_{s in S_t} softmax_{S_t}(score_h[t, .])[s] v_h[s];
  h = h + concat_h(a_h) W_o
  first_k_dense_replace layers: h = h + (silu(x W_g) * (x W_u)) W_d
  the others: p = sigmoid(x W_r) over the published experts, in float32; the
    top num_experts_per_tok of p + b; weights routed_scaling_factor * p_e /
    (sum of the chosen + 1e-6) (norm_topk_prob); h = h + the sum over the
    experts HELD here (``held_experts``) + the ungated shared expert
    (``reference_deltanet_mla_moe_decoder.expert_layer``, unchanged)
  logits = RMSNorm(h) W_head

Departures from the publication:
- no FP8: the index queries and keys are not quantised, and the Hadamard
  rotation in front of their quantiser is left out (an orthogonal map of
  both sides leaves q . k as it is);
- the multi-token-prediction module is not a layer of the served stack and
  is not here;
- weights are the PROGRAM's parameter tree (``models/llama.py::
  init_params``), read as float32, an int8 leaf times its scale: the served
  quantisation is shared by both sides;
- ``forward`` computes a block of queries at a time (``block``), so that 8
  layers at 6,144 and some 5,000 positions fit the chip beside the server;
  ``layers=(lo, hi), h=...`` runs a block of layers from a hidden state;
- ``selection=`` (a list a layer of ``[S, S]`` boolean arrays, or None for a
  layer) replaces the reference's own choice: the choice is discrete, so a
  program is compared with the reference ON THE PROGRAM'S OWN SETS, and
  its scores and sets with the reference's beside that; ``want="index"``
  also returns each layer's index scores and chosen sets;
- ``faults``: ``index_no_rope`` leaves the index heads unrotated,
  ``index_bf16`` rounds the index queries and keys to bfloat16
  (``lax.reduce_precision``), ``act_bf16`` every activation that enters a
  weight matrix or the attention's products, ``no_selection`` attends every
  key: what each tolerance has to catch.
"""

import jax
import jax.numpy as jnp

from benchmark.lib.reference_deltanet_mla_moe_decoder import (
    _f32, expert_layer, glu,
)
from benchmark.lib.reference_mla_moe_decoder import (
    rms_norm, rope_pairs, yarn_inv_freq,
)


def bf16(x):
    """Round float32 values to bfloat16's 8 bits of mantissa (an ``astype``
    there and back is taken out by the TPU's compiler)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def index_scores(x, c_q, lp, i, cfg, pos, inv_freq, faults):
    """``I [S, S]`` float32, -inf above the diagonal."""
    S = x.shape[0]
    Hi, Di, dr = (cfg["index_n_heads"], cfg["index_head_dim"],
                  cfg["qk_rope_head_dim"])

    def rope(t):
        if faults.get("index_no_rope"):
            return t
        return jnp.concatenate(
            [rope_pairs(t[..., :dr], pos, inv_freq, 1.0), t[..., dr:]],
            axis=-1)

    q = rope((c_q @ _f32(lp["wq_idx"], i)).reshape(S, Hi, Di))
    k = rope(layer_norm(
        x @ _f32(lp["wk_idx"], i), _f32(lp["k_idx_norm"], i),
        _f32(lp["k_idx_norm"], i, "bias"), 1e-6))
    w = (x @ _f32(lp["w_idx"], i)) * (Hi ** -0.5 * Di ** -0.5)
    if faults.get("index_bf16"):
        q, k, w = bf16(q), bf16(k), bf16(w)
    out = []
    for lo in range(0, S, 256):
        s = jnp.einsum("qhd,kd->qhk", q[lo:lo + 256], k)
        out.append(jnp.einsum("qhk,qh->qk", jnp.maximum(s, 0.0),
                              w[lo:lo + 256]))
    scores = jnp.concatenate(out, axis=0)
    return jnp.where(pos[:, None] >= pos[None, :], scores, -jnp.inf)


def choose(scores, topk):
    """``[S, S]`` boolean: row t's ``topk`` largest scores among s <= t, ties
    to the smaller s (a stable sort); every s <= t while t + 1 <= topk."""
    S = scores.shape[0]
    causal = jnp.isfinite(scores)
    if S <= topk:
        return causal
    order = jnp.argsort(-scores, axis=-1, stable=True)[:, :topk]
    top = jnp.zeros((S, S), bool).at[jnp.arange(S)[:, None], order].set(True)
    return top & causal


def latent_layer(x, lp, i, cfg, pos, inv_freq, faults, block, chosen=None,
                 want_index=False):
    """Sparse latent attention, unabsorbed.  Returns the branch's output and,
    with ``want_index``, the index scores and the chosen sets."""
    act = bf16 if faults.get("act_bf16") else (lambda t: t)
    S = x.shape[0]
    H, R = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    scale = (dn + dr) ** -0.5
    x = act(x)
    c_q = act(rms_norm(x @ _f32(lp["wq_a"], i), _f32(lp["q_a_norm"], i), eps))
    q = (c_q @ _f32(lp["wq_b"], i)).reshape(S, H, dn + dr)
    ck = x @ _f32(lp["wkv_a"], i)
    c = act(rms_norm(ck[:, :R], _f32(lp["kv_norm"], i), eps))
    kv = act((c @ _f32(lp["wkv_b"], i)).reshape(S, H, dn + dv))
    q_pe = act(rope_pairs(q[..., dn:], pos, inv_freq, 1.0))
    k_pe = act(rope_pairs(ck[:, R:], pos, inv_freq, 1.0))
    q = act(q)
    scores = None
    if chosen is None or want_index:
        scores = index_scores(x, c_q, lp, i, cfg, pos, inv_freq, faults)
    if chosen is None:
        chosen = choose(scores, cfg["index_topk"])
    if faults.get("no_selection"):
        chosen = pos[:, None] >= pos[None, :]
    chosen = jnp.asarray(chosen)
    out = []
    for lo in range(0, S, block):
        hi = min(lo + block, S)
        s = (jnp.einsum("qhd,khd->hqk", q[lo:hi, :, :dn], kv[..., :dn])
             + jnp.einsum("qhd,kd->hqk", q_pe[lo:hi], k_pe)) * scale
        s = jnp.where(chosen[lo:hi][None], s, -jnp.inf)
        out.append(jnp.einsum(
            "hqk,khd->qhd", act(jax.nn.softmax(s, -1)), kv[..., dn:]))
    a = act(jnp.concatenate(out, axis=0).reshape(S, H * dv))
    return a @ _f32(lp["wo"], i), scores, chosen


def rope_inv_freq(cfg):
    rope = cfg.get("rope_parameters") or {}
    return jnp.asarray(yarn_inv_freq(
        cfg["qk_rope_head_dim"],
        rope.get("rope_theta") or cfg.get("rope_theta", 10000.0), None))


def layer(h, lp, i, cfg, pos, dense, faults, block=256, chosen=None,
          want_index=False, inv_freq=None):
    """One block: ``lp`` the stack that holds it, ``i`` its index there,
    ``dense`` a dense feed-forward (else the experts).  Returns ``(h, index
    scores or None, the sets attended)``."""
    eps = cfg["rms_norm_eps"]
    act = bf16 if faults.get("act_bf16") else (lambda t: t)
    if inv_freq is None:
        inv_freq = rope_inv_freq(cfg)
    x = rms_norm(h, _f32(lp["attn_norm"], i), eps)
    y, scores, chosen = latent_layer(
        x, lp, i, cfg, pos, inv_freq, faults, block, chosen, want_index)
    h = h + y
    x = act(rms_norm(h, _f32(lp["mlp_norm"], i), eps))
    h = h + (glu(x, lp, i, 0.0) if dense
             else expert_layer(x, lp, i, cfg, faults))
    return h, scores, chosen


def forward(params, cfg, tokens, rows=None, layers=None, h=None, head=True,
            block=256, selection=None, want=None, **faults):
    """Logits ``[S, vocab]`` (``[len(rows), vocab]`` with ``rows``) of one
    sequence ``tokens [S]``; ``cfg`` has the Hugging Face keys of the
    configuration's JSON file.  ``selection[l]``: the sets layer ``l``
    attends in place of its own choice.  ``want="index"``: returns ``(logits,
    [scores a layer], [sets a layer])`` (float32 ``[S, S]``, -inf above the
    diagonal; boolean ``[S, S]``).  ``layers`` / ``h`` / ``head``: a block of
    layers, as the other references'."""
    n_dense = cfg.get("first_k_dense_replace", 0) if (
        "dense_layers" in params) else 0
    lo, hi = layers or (0, cfg["num_hidden_layers"])
    pos = jnp.arange(tokens.shape[0])
    eps = cfg["rms_norm_eps"]
    inv_freq = rope_inv_freq(cfg)
    act = bf16 if faults.get("act_bf16") else (lambda t: t)
    scores, sets = [], []
    with jax.default_matmul_precision("highest"):
        if h is None:
            emb = params["embed"]
            h = jnp.asarray(emb["weight"], jnp.float32)[tokens]
            if "embed_scale" in emb:
                h = h * jnp.asarray(emb["embed_scale"], jnp.float32)[tokens]
        for l in range(lo, hi):
            lp, i = ((params["dense_layers"], l) if l < n_dense
                     else (params["layers"], l - n_dense))
            h, sc, ch = layer(
                h, lp, i, cfg, pos, l < n_dense, faults, block,
                None if selection is None else selection[l],
                want == "index", inv_freq)
            scores.append(sc)
            sets.append(ch)
        if not head:
            return h
        if rows is not None:
            h = h[jnp.asarray(rows)]
        h = act(rms_norm(
            h, jnp.asarray(params["final_norm"]["weight"], jnp.float32), eps))
        head_p = params["lm_head"]
        w = jnp.asarray(head_p["weight"], jnp.float32)
        if "scale" in head_p:
            w = w * jnp.asarray(head_p["scale"], jnp.float32)
        logits = h @ w
    return (logits, scores, sets) if want == "index" else logits
