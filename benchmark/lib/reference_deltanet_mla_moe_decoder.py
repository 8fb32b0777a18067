"""The plain reference of ``gigachat3.5-432b-a28b-int8``: a hybrid decoder of
GATED DELTA-RULE layers (Gated Delta Networks, arXiv:2412.06464; the key names
of Qwen3-Next) beside LATENT-ATTENTION layers with a compressed, gated query
(DeepSeek-V3, arXiv:2412.19437; Gated Attention for LLMs, arXiv:2505.06708)
and a sigmoid-and-bias router over routed experts of which this rank holds
some, in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: the delta rule as the
token-by-token recurrence, attention unabsorbed with explicit K and V, the
experts as a loop.  No chunked form, no absorbed latent, no cache, no
batching, no kernel, no quantisation.

Equations.  ``n(x; w) = x / rms(x) * (1 + w)``, eps rms_norm_eps: every gain
is stored as an offset from 1 (``norm_type`` ZeroCentered...).  E hidden.
  h0 = Emb[tokens]
  a block (``layernorm_type`` pre_post), four norms a layer:
    h = h + n_b(Mixer(n_a(h)));   h = h + n_d(FFN(n_c(h)))
  Mixer, a layer NOT in full_attention_layers (gated delta rule; nk key heads
  and nv value heads of widths dk, dv; K = linear_conv_kernel_dim taps):
    [q|k|v] = silu(conv_K(x W_qkv)): causal, depthwise, zeros before the start
    z = x W_z;  beta = sigmoid(x W_b);  g = -exp(A_log) * softplus(x W_a + dt_bias)
    q, k = q / |q|, k / |k| a head (eps 1e-6 under the root);  q = q * dk^-0.5
    value head j reads key head j // (nv / nk), and keeps S [dk, dv] from 0:
      S' = exp(g_t) S;  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t
    y = n_h(o_t; w_o) * (linear_sigmoid_gate_scale * sigmoid(z_t)), n_h over
      the dv of a head, eps linear_attn_o_norm_eps;  Mixer = concat_j(y) W_o
  Mixer, a layer in full_attention_layers (latent attention; H heads; dn, dr,
  dv = qk_nope, qk_rope, v head widths; R = kv_lora_rank):
    c_q = n(x W_qa; w_qa);  q = c_q W_qb -> [S, H, dn + dr] = q_nope | q_pe
    ck = x W_kva -> [S, R + dr];  c = n(ck[:R]; w_kv);  k_pe = ck[R:]
    kv = c W_kvb -> [S, H, dn + dv] = k_nope | v
    q_pe, k_pe = RoPE(.): pairs (2i, 2i+1), YaRN inverse frequencies
    score_h[t,s] = (q_nope_h[t].k_nope_h[s] + q_pe_h[t].k_pe[s])
                   * (dn + dr)^-0.5 * m^2,  m = 0.1 mscale_all_dim ln factor + 1
    a_h = softmax_causal(score_h) v_h;  a = a * sigmoid(x W_g);  Mixer = a W_o
  FFN, the first first_k_dense_replace layers, and every expert:
    W_d(silu(min(a, L)) * clip(b, -L, L)),  a = x W_gate, b = x W_up, L = swiglu_limit
  FFN, the other layers:  s = sigmoid(x W_r) over ALL n routed experts;
    idx = top-k(s + bias);  w = routed_scaling_factor * s[idx] / (sum s[idx] + 1e-6)
    FFN = sum_{j: idx_j held here} w_j Expert_idx_j(x) + Shared(x)
  logits = n(h; w_f) W_head      (untied)

Departures from the published description, each also in the configuration
file's ``assumed`` (the config reuses the key names of two published families
and spells out none of these):
- the norm class's GATE acts only where it has a second input, the delta
  layer's output norm, and ``layernorm_gating_weight`` 2 is that gate's scale
  (the same 2 as ``linear_sigmoid_gate_scale``);
- ``use_mla_scaling_factor``: DeepSeek's convention, the scores times m^2;
- ``gated_attention``: the paper's elementwise head-specific sigmoid gate from
  the layer's input with a projection of its own (a compressed query has no
  doubled q_proj to split);
- ``swiglu_limit``: gpt-oss's clamp without its alpha and ``+ 1`` (hidden_act
  is silu);
- no ``scoring_func`` key: sigmoid scores selected on score + bias (the
  family's), the ``1e-6`` in the divisor this repo's third router's;
- the multi-token-prediction modules are not run (the published inference
  path without speculation does not run them);
- HELD EXPERTS: ``cfg["held_experts"] = [lo, hi)``: the parameter tree holds
  those experts of ``published_n_routed_experts``; the router scores them
  all, and the layer's sum runs over the held ones (with the shared expert,
  which every rank computes): one expert-parallel rank's part of the layer,
  what this chip computes.  Without the key every expert is here;
- weights are the PROGRAM's parameter tree (``models/llama.py::init_params``),
  read as float32, an int8 leaf times its scale: the served quantisation is
  shared by both sides, so a comparison shows the program's bf16 activations,
  its chunked form, its state pool, its kernels and its latent cache, and not
  the quantisation;
- attention scores are computed in BLOCKS of queries (``block`` rows at a
  time, every key at once), so that two thousand tokens at 64 heads fit;
- ``forward(..., layers=(lo, hi), h=...)`` runs a block of layers from a
  hidden state (the blocks chained give the full forward); ``rows`` picks the
  positions whose logits come back;
- the faults a tolerance must catch, each off by default: ``state_bf16`` (S
  rounded to bfloat16 after every token), ``beta=False`` (every write at full
  strength), ``decay=False`` (g = 0: nothing decays), ``attn_gate=False`` (no
  gate on the attention's output), ``drop_expert=e`` (held expert ``e``'s
  part left out of every layer's sum; ``"all"``: every held expert's), ``zero_state_at`` (every delta layer
  forgets, state and conv tail, at that position: a state lost between two
  chunks); and ``shared=False``, no fault: the shared expert left out, for
  the test that adds the ranks' routed parts and counts it once.
"""

import jax
import jax.numpy as jnp

from benchmark.lib.reference_hybrid_conv_moe_decoder import (
    layer_homes, short_conv,
)
from benchmark.lib.reference_mla_moe_decoder import (
    _f32_expert, mscale, rope_pairs, yarn_inv_freq,
)


def norm(x, w, eps):
    """Zero-centred RMSNorm: the gain is ``1 + w``."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _f32(leaf, i, key="weight"):
    w = jnp.asarray(leaf[key][i], jnp.float32)
    if "scale" in leaf:
        w = w * jnp.asarray(leaf["scale"][i], jnp.float32)
    return w


def glu(x, p, i, L, expert=None):
    def w(name):
        return (_f32(p[name], i) if expert is None
                else _f32_expert(p[name], i, expert))

    a, b = x @ w("w_gate"), x @ w("w_up")
    if L:
        a, b = jnp.minimum(a, L), jnp.clip(b, -L, L)
    return (jax.nn.silu(a) * b) @ w("w_down")


def delta_layer(x, lp, i, cfg, faults):
    """The gated delta-rule mixer over one whole sequence ``x [S, E]``."""
    S = x.shape[0]
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    qkv = jax.nn.silu(short_conv(
        x @ _f32(lp["in_qkv"], i), _f32(lp["conv"], i, "taps"),
        faults.get("zero_state_at")))
    q, k, v = jnp.split(qkv, [nk * dk, 2 * nk * dk], axis=-1)

    def unit(u):
        u = u.reshape(S, nk, dk)
        return u * jax.lax.rsqrt(jnp.sum(u * u, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(q) * dk ** -0.5, nv // nk, axis=1)
    k = jnp.repeat(unit(k), nv // nk, axis=1)
    v = v.reshape(S, nv, dv)
    beta = jax.nn.sigmoid(x @ _f32(lp["in_b"], i))
    g = -jnp.exp(jnp.asarray(lp["A_log"]["bias"][i], jnp.float32)) * (
        jax.nn.softplus(x @ _f32(lp["in_a"], i) + jnp.asarray(
            lp["dt_bias"]["bias"][i], jnp.float32)))
    if not faults.get("beta", True):
        beta = jnp.ones_like(beta)
    if not faults.get("decay", True):
        g = jnp.zeros_like(g)
    lost = faults.get("zero_state_at")

    def token(St, xs):
        q_t, k_t, v_t, g_t, b_t, t = xs
        if lost is not None:
            St = jnp.where(t == lost, 0.0, St)
        St = jnp.exp(g_t)[:, None, None] * St
        held = jnp.einsum("hk,hkv->hv", k_t, St)
        St = St + k_t[:, :, None] * (b_t[:, None] * (v_t - held))[:, None, :]
        if faults.get("state_bf16"):
            # (not a cast there and back: the TPU's compiler takes such a
            # pair out as excess precision it is allowed to keep)
            St = jax.lax.reduce_precision(St, exponent_bits=8,
                                          mantissa_bits=7)
        return St, jnp.einsum("hk,hkv->hv", q_t, St)

    _, o = jax.lax.scan(token, jnp.zeros((nv, dk, dv), jnp.float32),
                        (q, k, v, g, beta, jnp.arange(S)))
    y = norm(o, _f32(lp["o_norm"], i), cfg["linear_attn_o_norm_eps"])
    y = y * (cfg["linear_sigmoid_gate_scale"] * jax.nn.sigmoid(
        (x @ _f32(lp["in_z"], i)).reshape(S, nv, dv)))
    return y.reshape(S, nv * dv) @ _f32(lp["out_proj"], i)


def latent_layer(x, lp, i, cfg, pos, inv_freq, faults, block):
    """Latent attention, unabsorbed: every head's K and V built from the
    latent, the scores a block of queries at a time."""
    S = x.shape[0]
    H, R = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    rs = cfg.get("rope_scaling") or {}
    f = rs.get("factor", 1)
    m_all = mscale(f, rs.get("mscale_all_dim", 0))
    rot = mscale(f, rs.get("mscale", 1)) / m_all
    scale = (dn + dr) ** -0.5 * (
        m_all ** 2 if cfg.get("use_mla_scaling_factor", True) else 1.0)
    c_q = norm(x @ _f32(lp["wq_a"], i), _f32(lp["q_a_norm"], i), eps)
    q = (c_q @ _f32(lp["wq_b"], i)).reshape(S, H, dn + dr)
    ck = x @ _f32(lp["wkv_a"], i)
    c = norm(ck[:, :R], _f32(lp["kv_norm"], i), eps)
    kv = (c @ _f32(lp["wkv_b"], i)).reshape(S, H, dn + dv)
    q_pe = rope_pairs(q[..., dn:], pos, inv_freq, rot)
    k_pe = rope_pairs(ck[:, R:], pos, inv_freq, rot)
    out = []
    for lo in range(0, S, block):
        hi = min(lo + block, S)
        s = (jnp.einsum("qhd,khd->hqk", q[lo:hi, :, :dn], kv[..., :dn])
             + jnp.einsum("qhd,kd->hqk", q_pe[lo:hi], k_pe)) * scale
        s = jnp.where((pos[lo:hi, None] >= pos[None, :])[None], s, -jnp.inf)
        out.append(jnp.einsum(
            "hqk,khd->qhd", jax.nn.softmax(s, -1), kv[..., dn:]))
    a = jnp.concatenate(out, axis=0).reshape(S, H * dv)
    if faults.get("attn_gate", True) and cfg.get("gated_attention"):
        a = a * jax.nn.sigmoid(x @ _f32(lp["attn_gate"], i))
    return a @ _f32(lp["wo"], i)


def expert_layer(x, lp, i, cfg, faults):
    """The router over all the experts, the sum over those held here, and
    the shared expert."""
    L = cfg.get("swiglu_limit") or 0.0
    s = jax.nn.sigmoid(x @ _f32(lp["router"], i))                  # [S, n]
    bias = jnp.asarray(lp["expert_bias"]["bias"][i], jnp.float32)
    _, idx = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    w = w * cfg.get("routed_scaling_factor", 1.0)
    lo, hi = cfg.get("held_experts") or (
        0, lp["experts"]["w_gate"]["weight"].shape[1])
    out = glu(x, lp["shared"], i, L) if "shared" in lp else 0.0
    if faults.get("shared", True) is False:
        out = 0.0
    for e in range(lo, hi):                    # a loop over the held experts
        if faults.get("drop_expert") in (e, "all"):
            continue
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)        # [S]
        out = out + w_e[:, None] * glu(x, lp["experts"], i, L, e - lo)
    return out


def layer(h, lp, i, cfg, pos, attn, dense, faults, block=256, inv_freq=None):
    """One block: ``lp`` the stack that holds it, ``i`` its index there,
    ``attn`` a latent layer (else the delta rule), ``dense`` a dense FFN
    (else the experts)."""
    eps = cfg["rms_norm_eps"]
    if inv_freq is None:
        inv_freq = jnp.asarray(yarn_inv_freq(
            cfg["qk_rope_head_dim"], cfg["rope_theta"],
            cfg.get("rope_scaling")))
    x = norm(h, _f32(lp["attn_norm"], i), eps)
    if attn:
        y = latent_layer(x, lp, i, cfg, pos, inv_freq, faults, block)
    else:
        y = delta_layer(x, lp, i, cfg, faults)
    h = h + norm(y, _f32(lp["attn_post_norm"], i), eps)
    x = norm(h, _f32(lp["mlp_norm"], i), eps)
    if dense:
        y = glu(x, lp, i, cfg.get("swiglu_limit") or 0.0)
    else:
        y = expert_layer(x, lp, i, cfg, faults)
    return h + norm(y, _f32(lp["mlp_post_norm"], i), eps)


def kinds(cfg):
    """``layer_homes``'s view of this configuration: the mixer of every
    layer, and the count of leading dense layers."""
    full = set(cfg["full_attention_layers"])
    return {"layer_types": ["attn" if l in full else "deltanet"
                            for l in range(cfg["num_hidden_layers"])],
            "num_dense_layers": cfg.get("first_k_dense_replace", 0)}


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
    inv_freq = jnp.asarray(yarn_inv_freq(
        cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg.get("rope_scaling")))
    with jax.default_matmul_precision("highest"):
        if h is None:
            emb = params["embed"]
            h = jnp.asarray(emb["weight"], jnp.float32)[tokens]
            if "embed_scale" in emb:
                h = h * jnp.asarray(emb["embed_scale"], jnp.float32)[tokens]
        for l in range(lo, hi):
            key, i = homes[l]
            h = layer(h, params[key], i, cfg, pos,
                      view["layer_types"][l] == "attn",
                      l < view["num_dense_layers"], faults, block, inv_freq)
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
