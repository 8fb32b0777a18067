"""The plain reference of ``lfm2-8b-a1b-int8``: LFM2-8B-A1B's published
forward pass (``transformers`` ``models/lfm2_moe/modeling_lfm2_moe.py``) in
straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: a convolution as an explicit sum
over shifted copies of the whole sequence, full causal attention, a loop
over experts; no cache, kernels, batching or quantisation.

Equations (``eps`` = norm_eps; E hidden; H / KVH heads of width D = E / H
(``head_dim`` where a file gives one); K = conv_L_cache; X experts, top-k):
  h0 = Emb[tokens]
  per layer:
    u = RMSNorm_operator(h)
    layer_types[l] == "conv":
      (B, C, x) = split3(u W_in)        each [S, E], in this order, no bias
      z = B * x
      y_t = sum_{i=0..K-1} w[:, i] * z_{t-(K-1)+i}    depthwise, w [E, K],
            zeros before the sequence's start, no bias, no activation
      h = h + (C * y) W_out
    layer_types[l] == "full_attention":
      q, k, v = u W_q, u W_k, u W_v     [S, H, D], [S, KVH, D], [S, KVH, D]
      q, k = RMSNorm_q(q), RMSNorm_k(k) over D (weights [D])
      q, k = RoPE(q), RoPE(k): rotate-half over all of D, theta rope_theta
      a_h = softmax_causal(q_h . k_g(h) / sqrt(D)) v_g(h)
      h = h + concat_h(a_h) W_o
    x = RMSNorm_ffn(h)
    l < num_dense_layers:   h = h + (silu(x W_g) * (x W_u)) W_d
    else:  s = sigmoid(x W_r) over the X experts, in float32
           idx = top-k(s + b) with b the learned expert_bias [X]
           g = s[idx] (WITHOUT the bias); norm_topk_prob: g = g / (sum g +
           1e-6); g = g * routed_scaling_factor
           h = h + sum_j g_j Expert_idx_j(x), each expert a SwiGLU
  logits = RMSNorm_embedding(h) Emb^T      (the head is the embedding, tied)

Departures from the publication:
- weights are the PROGRAM's parameter tree (``models/llama.py::init_params``:
  one stack a run of one kind of layer, a new run wherever ``layer_types``
  or the dense/expert FFN changes, runs that repeat back to back sharing a
  stack: ``layer_homes``), read as float32,
  an int8 leaf times its scale: the served quantisation is shared by both
  sides, so a comparison shows the program's bf16 activations, its kernels,
  its page pool and its state pool, and not the quantisation;
- ``forward(..., layers=(lo, hi), h=...)`` runs a block of layers from a
  hidden state, so that at the published size the reference fits beside the
  server's weights on the chip; the blocks chained give the full forward;
- the faults a tolerance must catch, each off by default: ``act`` (applied
  to every activation that enters a weight matrix: ``round_to_8_bits`` is an
  8-bit activation path), ``top_k`` (3 for 4: a dropped expert),
  ``zero_state_at`` (a position at which every conv layer forgets what came
  before: a conv state lost at a chunk boundary or a prefix hit),
  ``expert_bias=False`` (selection on the scores alone) and
  ``qk_norm=False``.
"""

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope_half(x, positions, theta):
    """Rotate-half RoPE over the whole last axis of ``x [S, heads, D]``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)                                       # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _f32(leaf, i=None, key="weight"):
    """A weight dict of the program's tree as float32 (layer ``i`` of a
    stack): an int8 leaf times its per-channel scale."""
    w = leaf[key] if i is None else leaf[key][i]
    w = jnp.asarray(w, jnp.float32)
    for name in ("scale", "embed_scale"):
        if name in leaf:
            s = leaf[name] if i is None else leaf[name][i]
            w = w * jnp.asarray(s, jnp.float32)
    return w


def _f32_expert(leaf, i, e):
    w = jnp.asarray(leaf["weight"][i, e], jnp.float32)
    if "scale" in leaf:
        w = w * jnp.asarray(leaf["scale"][i, e], jnp.float32)
    return w


def round_to_8_bits(x):
    """Symmetric per-row 8-bit rounding: what a W8A8 path does to x."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 127
    return jnp.round(x / s) * s


def layer_homes(cfg):
    """``[(stack key, index in the stack)]`` of every layer, as the program
    lays its tree out: a run is the layers of one kind (mixer x FFN) that
    follow each other; runs that repeat back to back (the longest such
    period from where the last group ended) share a stack a run of the
    period, named for the period's first occurrence, repetition-major."""
    kinds = [(t, l >= cfg.get("num_dense_layers", 0))
             for l, t in enumerate(cfg["layer_types"])]
    runs, l = [], 0                                   # (kind, count)
    while l < len(kinds):
        m = l
        while m < len(kinds) and kinds[m] == kinds[l]:
            m += 1
        runs.append((kinds[l], m - l))
        l = m
    homes, i = [], 0
    while i < len(runs):
        p, reps = 1, 1
        for q in range(1, (len(runs) - i) // 2 + 1):
            k = 1
            while runs[i + k * q:i + (k + 1) * q] == runs[i:i + q]:
                k += 1
            if k > 1 and k * q > p * reps:
                p, reps = q, k
        for r in range(reps):
            for j, (_, count) in enumerate(runs[i:i + p]):
                homes += [(f"run{i + j:02d}", r * count + n)
                          for n in range(count)]
        i += p * reps
    return homes


def short_conv(z, w, zero_state_at=None):
    """``y_t = sum_i w[:, i] z_{t-(K-1)+i}`` over a whole sequence ``z [S,
    E]``: an explicit sum over shifted copies, zeros before the start (and,
    for the fault, before ``zero_state_at`` for the tokens from it on)."""
    S, K = z.shape[0], w.shape[-1]
    t = jnp.arange(S)
    y = 0.0
    for i in range(K):
        d = K - 1 - i
        shifted = jnp.pad(z, ((d, 0), (0, 0)))[:S]
        if zero_state_at is not None and d:
            lost = (t >= zero_state_at) & (t - d < zero_state_at)
            shifted = jnp.where(lost[:, None], 0.0, shifted)
        y = y + w[:, i] * shifted
    return y


def route(x, w_r, bias, cfg, top_k=None):
    """``(weights [S, k], experts [S, k])`` of the sigmoid-and-bias router."""
    s = jax.nn.sigmoid(x @ w_r)                                   # [S, X]
    chosen = s if bias is None else s + bias
    _, idx = jax.lax.top_k(chosen, top_k or cfg["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-6)
    return g * cfg.get("routed_scaling_factor", 1.0), idx


def _layer(h, lp, i, conv, moe, cfg, pos, act, top_k, zero_state_at,
           expert_bias, qk_norm):
    """One layer; ``lp`` is the stack that holds it, ``i`` its index there."""
    S, E = h.shape
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg.get("head_dim") or E // H
    eps = cfg["norm_eps"]

    u = act(rms_norm(h, _f32(lp["attn_norm"], i), eps))
    if conv:
        b, c, x = jnp.split(u @ _f32(lp["in_proj"], i), 3, axis=-1)
        y = short_conv(b * x, _f32(lp["conv"], i, "taps"), zero_state_at)
        h = h + act(c * y) @ _f32(lp["out_proj"], i)
    else:
        q = (u @ _f32(lp["wq"], i)).reshape(S, H, D)
        k = (u @ _f32(lp["wk"], i)).reshape(S, KVH, D)
        v = (u @ _f32(lp["wv"], i)).reshape(S, KVH, D)
        if qk_norm:
            q = rms_norm(q, _f32(lp["q_norm"], i), eps)
            k = rms_norm(k, _f32(lp["k_norm"], i), eps)
        q = rope_half(q, pos, cfg["rope_theta"])
        k = rope_half(k, pos, cfg["rope_theta"])
        k, v = (jnp.repeat(a, H // KVH, axis=1) for a in (k, v))
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(D)
        s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        h = h + act(a.reshape(S, H * D)) @ _f32(lp["wo"], i)

    x = act(rms_norm(h, _f32(lp["mlp_norm"], i), eps))
    if not moe:
        return h + act(jax.nn.silu(x @ _f32(lp["w_gate"], i)) * (
            x @ _f32(lp["w_up"], i))) @ _f32(lp["w_down"], i)
    bias = None
    if expert_bias and cfg.get("use_expert_bias"):
        bias = jnp.asarray(lp["expert_bias"]["bias"][i], jnp.float32)
    g, idx = route(x, _f32(lp["router"], i), bias, cfg, top_k)
    ex, out = lp["experts"], 0.0
    for e in range(cfg["num_experts"]):             # a loop over experts
        w_e = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)          # [S]
        y = act(jax.nn.silu(x @ _f32_expert(ex["w_gate"], i, e)) * (
            x @ _f32_expert(ex["w_up"], i, e))) @ _f32_expert(
                ex["w_down"], i, e)
        out = out + w_e[:, None] * y
    return h + out


def forward(params, cfg, tokens, layers=None, h=None, head=True,
            act=lambda x: x, top_k=None, zero_state_at=None,
            expert_bias=True, qk_norm=True):
    """Logits [S, vocab] of one sequence ``tokens`` [S]; ``cfg`` has the
    Hugging Face keys of the configuration's JSON file.

    ``layers=(lo, hi)`` runs layers lo..hi-1 only: from the embedding if
    ``h`` is None, else from the hidden state ``h`` [S, E]; ``head=False``
    returns the hidden state instead of logits (for the next block)."""
    homes = layer_homes(cfg)
    lo, hi = layers or (0, cfg["num_hidden_layers"])
    pos = jnp.arange(tokens.shape[0])
    with jax.default_matmul_precision("highest"):
        if h is None:
            h = _f32(params["embed"])[tokens]
        for layer in range(lo, hi):
            key, i = homes[layer]
            h = _layer(
                h, params[key], i, cfg["layer_types"][layer] == "conv",
                layer >= cfg.get("num_dense_layers", 0), cfg, pos, act,
                top_k, zero_state_at, expert_bias, qk_norm)
        if not head:
            return h
        h = act(rms_norm(h, jnp.asarray(params["final_norm"]["weight"],
                                        jnp.float32), cfg["norm_eps"]))
        return h @ _f32(params["embed"]).T
