"""The plain reference of ``deepseek-v2-lite-int8``: DeepSeek-V2-Lite's
published forward pass in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: decompressed attention, a loop
over experts, no cache, kernels, batching or quantisation.

Equations (``eps`` = rms_norm_eps; H heads; dn, dr, dv = qk_nope, qk_rope, v
head widths; R = kv_lora_rank):
  h0 = E[tokens]
  per layer:
    x  = RMSNorm(h)
    q  = x W_q                -> [S, H, dn + dr] = q_nope | q_pe
    ck = x W_kva              -> [S, R + dr];  c = RMSNorm_kv(ck[:R]);  k_pe = ck[R:]
    kv = c W_kvb              -> [S, H, dn + dv] = k_nope | v
    q_pe, k_pe = RoPE(q_pe), RoPE(k_pe): pairs (2i, 2i+1); YaRN inverse
        frequencies (factor, original_max_position_embeddings, beta_fast,
        beta_slow, rope_theta); cos and sin times mscale(f, mscale) /
        mscale(f, mscale_all_dim), where mscale(f, m) = 0.1 m ln f + 1
    score_h[t,s] = (q_nope_h[t].k_nope_h[s] + q_pe_h[t].k_pe[s])
                   * (dn + dr)^-0.5 * mscale(f, mscale_all_dim)^2
    a_h = softmax_causal(score_h) v_h ;  h = h + concat_h(a_h) W_o
    x  = RMSNorm(h)
    first_k_dense_replace layers:  h = h + (silu(x W_g) * (x W_u)) W_d
    the others:  s = softmax(x W_r) over all routed experts, in float32;
        idx = top-k(s); g = s[idx] * routed_scaling_factor (no
        renormalisation: norm_topk_prob false)
        h = h + sum_j g_j Expert_idx_j(x) + SharedMLP(x)
  logits = RMSNorm(h) W_head

Departures from the publication:
- the shared experts are one MLP of width n_shared_experts *
  moe_intermediate_size, as the published code builds them;
- weights are the PROGRAM's parameter tree (``models/llama.py::init_params``:
  ``dense_layers`` and ``layers`` stacks), read as float32, an int8 leaf
  times its scale: the served quantisation is shared by both sides, so what
  a comparison shows is the program's bf16 activations, its kernels and its
  cache, and not the quantisation;
- ``forward(..., layers=(lo, hi), h=...)`` runs a block of layers from a
  hidden state, so that at the published size the reference fits beside the
  server's weights on the chip; the blocks chained give the full forward;
- ``act`` (identity) is applied to every activation that enters a weight
  matrix; ``top_k`` overrides num_experts_per_tok.  Both exist so that the
  chip smoke can show what a tolerance would catch: ``act=round_to_8_bits``
  is an 8-bit activation path, ``top_k=5`` a dropped expert.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def yarn_inv_freq(dim, theta, rs):
    """YaRN inverse frequencies of a rotary width ``dim`` (closed form of
    the published ``DeepseekV2YarnRotaryEmbedding``)."""
    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / theta ** (i / dim)
    if not rs or rs.get("type", rs.get("rope_type")) != "yarn":
        return extra.astype(np.float32)
    inter = extra / rs["factor"]
    orig = rs["original_max_position_embeddings"]

    def corr(n_rot):
        return dim * math.log(orig / (n_rot * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def mscale(factor, m):
    return 1.0 if factor <= 1 or not m else 0.1 * m * math.log(factor) + 1.0


def rope_pairs(x, positions, inv_freq, scale):
    """Rotate the pairs (2i, 2i+1) of the last axis, in place."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq        # [S, d/2]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape)


def _f32(leaf, i=None):
    """A weight dict of the program's tree as float32 (layer ``i`` of a
    stack): an int8 leaf times its per-channel scale."""
    w = leaf["weight"] if i is None else leaf["weight"][i]
    w = jnp.asarray(w, jnp.float32)
    for key in ("scale", "embed_scale"):
        if key in leaf:
            s = leaf[key] if i is None else leaf[key][i]
            w = w * jnp.asarray(s, jnp.float32)
    return w


def round_to_8_bits(x):
    """Symmetric per-row 8-bit rounding: what a W8A8 path does to x."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 127
    return jnp.round(x / s) * s


def _mlp(x, p, i, act):
    x = act(x)
    return act(jax.nn.silu(x @ _f32(p["w_gate"], i)) * (
        x @ _f32(p["w_up"], i))) @ _f32(p["w_down"], i)


def _layer(h, lp, i, cfg, pos, inv_freq, act, top_k):
    """One layer; ``lp`` is the stack that holds it, ``i`` its index there."""
    S = h.shape[0]
    H = cfg["num_attention_heads"]
    R = cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    rs = cfg.get("rope_scaling") or {}
    f = rs.get("factor", 1)
    rot = mscale(f, rs.get("mscale", 1)) / mscale(
        f, rs.get("mscale_all_dim", 0))
    scale = (dn + dr) ** -0.5 * mscale(f, rs.get("mscale_all_dim", 0)) ** 2

    x = act(rms_norm(h, _f32(lp["attn_norm"], i), eps))
    q = (x @ _f32(lp["wq"], i)).reshape(S, H, dn + dr)
    ck = x @ _f32(lp["wkv_a"], i)
    c = rms_norm(ck[:, :R], _f32(lp["kv_norm"], i), eps)
    kv = (act(c) @ _f32(lp["wkv_b"], i)).reshape(S, H, dn + dv)
    q_pe = rope_pairs(q[..., dn:], pos, inv_freq, rot)
    k_pe = rope_pairs(ck[:, R:], pos, inv_freq, rot)
    s = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kv[..., :dn])
         + jnp.einsum("qhd,kd->hqk", q_pe, k_pe)) * scale
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kv[..., dn:])
    h = h + act(a.reshape(S, H * dv)) @ _f32(lp["wo"], i)

    x = rms_norm(h, _f32(lp["mlp_norm"], i), eps)
    if "router" not in lp:
        return h + _mlp(x, lp, i, act)
    probs = jax.nn.softmax(x @ _f32(lp["router"], i), axis=-1)      # [S, X]
    g, idx = jax.lax.top_k(probs, top_k or cfg["num_experts_per_tok"])
    g = g * cfg.get("routed_scaling_factor", 1.0)
    if cfg.get("norm_topk_prob"):
        g = g / jnp.sum(g, -1, keepdims=True)
    out = _mlp(x, lp["shared"], i, act) if "shared" in lp else 0.0
    ex = lp["experts"]
    x = act(x)
    for e in range(cfg["n_routed_experts"]):        # a loop over experts
        w_e = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)          # [S]
        y = act(jax.nn.silu(x @ _f32_expert(ex["w_gate"], i, e)) * (
            x @ _f32_expert(ex["w_up"], i, e))) @ _f32_expert(
                ex["w_down"], i, e)
        out = out + w_e[:, None] * y
    return h + out


def _f32_expert(leaf, i, e):
    w = jnp.asarray(leaf["weight"][i, e], jnp.float32)
    if "scale" in leaf:
        w = w * jnp.asarray(leaf["scale"][i, e], jnp.float32)
    return w


def forward(params, cfg, tokens, layers=None, h=None, head=True,
            act=lambda x: x, top_k=None):
    """Logits [S, vocab] of one sequence ``tokens`` [S]; ``cfg`` has the
    Hugging Face keys of the configuration's JSON file.

    ``layers=(lo, hi)`` runs layers lo..hi-1 only: from the embedding if
    ``h`` is None, else from the hidden state ``h`` [S, E]; ``head=False``
    returns the hidden state instead of logits (for the next block)."""
    n_dense = cfg.get("first_k_dense_replace", 0) if (
        "dense_layers" in params) else 0
    L = cfg["num_hidden_layers"]
    lo, hi = layers or (0, L)
    pos = jnp.arange(tokens.shape[0])
    inv_freq = jnp.asarray(yarn_inv_freq(
        cfg["qk_rope_head_dim"], cfg["rope_theta"],
        cfg.get("rope_scaling")))
    with jax.default_matmul_precision("highest"):
        if h is None:
            h = _f32(params["embed"])[tokens]
        for layer in range(lo, hi):
            if layer < n_dense:
                h = _layer(h, params["dense_layers"], layer, cfg, pos,
                           inv_freq, act, top_k)
            else:
                h = _layer(h, params["layers"], layer - n_dense, cfg, pos,
                           inv_freq, act, top_k)
        if not head:
            return h
        h = act(rms_norm(h, jnp.asarray(params["final_norm"]["weight"],
                                        jnp.float32), cfg["rms_norm_eps"]))
        w_head = (_f32(params["embed"]).T if cfg.get("tie_word_embeddings")
                  else _f32(params["lm_head"]))
        return h @ w_head
