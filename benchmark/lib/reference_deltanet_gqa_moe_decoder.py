"""The plain reference of ``qwen3-next-80b-a3b-int8``: a hybrid decoder of
GATED DELTA-RULE layers (Gated Delta Networks, arXiv:2412.06464;
``Qwen3NextGatedDeltaNet`` of the ``transformers`` model file) beside GATED
GQA ATTENTION layers (``Qwen3NextAttention``; the gate is arXiv:2505.06708's)
and, in every layer, a softmax router over routed experts of which this rank
holds some, beside a shared expert under a sigmoid gate, in straightforward
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``:
the delta rule as the token-by-token recurrence, attention with explicit
scores, every held expert a dense product over every token.  No chunked form,
no cache, no batching, no kernel, no sort, no quantisation.

Equations.  ``n(x; w) = x / rms(x) * (1 + w)``, eps rms_norm_eps: the block's
norms, the final norm and the q/k norms store their gain as an offset from 1.
E hidden.
  h0 = Emb[tokens]
  a block, two norms a layer:  h = h + Mixer(n_a(h));  h = h + MoE(n_c(h))
  Mixer, a delta layer (nk key heads and nv value heads of widths dk, dv; K =
  linear_conv_kernel_dim taps; x the normed input):
    [q|k|v] = silu(conv_K(x W_qkv)): causal, depthwise, zeros before the
      start, no bias
    z = x W_z;  beta = sigmoid(x W_b);  g = -exp(A_log) * softplus(x W_a + dt_bias)
    q, k = q / |q|, k / |k| a head (eps 1e-6 under the root);  q = q * dk^-0.5
    value head j reads key head j // (nv / nk), and keeps S [dk, dv] from 0:
      S' = exp(g_t) S;  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t
    y = (o_t / rms(o_t) * w_o) * silu(z_t): the norm over a head's dv with a
      PLAIN gain (no ``1 +``), eps rms_norm_eps;  Mixer = concat_j(y) W_o
  Mixer, an attention layer (layer l with (l + 1) % full_attention_interval
  == 0; H query heads over KV kv heads of D = head_dim):
    q_h = x W_q,  gate_h = x W_g  (the two halves of the published q_proj, a
      head's [q | gate]);  k, v = x W_k, x W_v
    q_h = n_q(q_h), k_h = n_k(k_h): over the D dims, gains shared by the heads
    rope over the FIRST partial_rotary_factor * D dims of each, pairs (i, i +
      width / 2), theta rope_theta, no scaling; the rest pass
    score = D^-0.5 q . k, causal; query head h reads kv head h // (H / KV)
    a = [o_h * sigmoid(gate_h)] W_o: the gate a head AND channel
  MoE:  p = softmax(x W_r) over ALL num_experts in float32;  idx = top-k(p);
    w = p[idx] / sum p[idx]  (norm_topk_prob)
    MoE = sum_{j: idx_j held here} w_j Expert_idx_j(x)
          + sigmoid(x w_sg) * Shared(x),   every expert W_d(silu(x W_g) * x W_u)
  logits = n(h; w_f) W_head      (untied)

Departures from the published description, each also in the configuration
file's ``assumed``:
- the gate's half of ``q_proj`` is a matrix of its own (``attn_gate``; the
  loader splits the published file's doubled projection a head: the same
  bytes, the same product);
- ``in_proj_qkvz`` and ``in_proj_ba`` (interleaved a key-head group in the
  published file) are the four matrices ``in_qkv`` (columns q | k | v, each
  heads-major), ``in_z``, ``in_b``, ``in_a``: a permutation of columns;
- the multi-token-prediction module is not run (the published inference path
  without speculation does not run it);
- HELD EXPERTS: ``held=(lo, hi)`` (default ``cfg["held_experts"]``): the
  parameter tree holds those experts of ``published_num_experts``; the router
  scores them all, and the layer's sum runs over the held ones (with the
  gated shared expert, which every rank computes): one expert-parallel rank's
  part of the layer, what this chip computes.  Without either every expert is
  here;
- weights are the PROGRAM's parameter tree (``models/llama.py::init_params``),
  read as float32, an int8 leaf times its scale: the served quantisation is
  shared by both sides, so a comparison shows the program's bf16 activations,
  its chunked form, its state pool, its kernels and its pages, and not the
  quantisation;
- attention scores are computed in BLOCKS of queries (``block`` rows at a
  time, every key at once) and the held experts one after the other in a
  ``lax.scan`` (each a dense product over every token, times its weight or
  0), so that twelve layers at 2,048 and five thousand positions fit a chip
  and 256 experts a layer are one loop body to compile;
- ``choices [L, S, k]`` (int, a row of -1: none): the experts to use at a
  layer and position IN PLACE of the reference's own top-k, weighted by the
  reference's own probabilities of them (renormalised over them): ten of 512
  near-tied probabilities flip under bfloat16, and a comparison by logits is
  tight only where both sides sum the same experts;
  ``return_router=True``: also ``{"own" [L, S, k], "p_own" [L, S, k],
  "p_used" [L, S, k], "used" [L, S, k]}``, the reference's own top-k, its
  probabilities of them (descending: the last is its k-th), and of the
  experts it used;
- ``forward(..., layers=(lo, hi), h=...)`` runs a block of layers from a
  hidden state; ``rows`` picks the positions whose logits come back;
- the faults a tolerance must catch, each off by default: ``gate_per_head``
  (the attention gate's mean over a head's channels in place of a channel's
  own), ``rope_all`` (rope over all of a head), ``qk_norm=False`` (q/k norms
  dropped), ``delta_gate="2sigmoid"`` (``2 sigmoid(z)`` in place of
  ``silu(z)``), ``delta_norm_offset`` (the delta norm's gain read ``1 + w``),
  ``shared_gate=False`` (the shared expert ungated), ``renormalize=False``
  (the chosen probabilities as they are), ``drop_expert=e`` (held expert
  ``e``'s part left out of every layer's sum; ``"all"``: every held
  expert's), ``state_bf16`` (S rounded to bfloat16 after every token),
  ``zero_state_at`` (every delta layer forgets, state and conv tail, at that
  position: a state lost between two chunks); and ``shared=False``, no fault:
  the shared expert left out, for the test that adds the ranks' routed parts
  and counts it once.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.reference_deltanet_mla_moe_decoder import _f32
from benchmark.lib.reference_hybrid_conv_moe_decoder import (
    layer_homes, short_conv,
)
from benchmark.lib.reference_window_moe_decoder import rope


def norm(x, w, eps, offset=1.0):
    """RMSNorm with the gain ``offset + w``: zero-centred at 1, plain at 0."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (offset + w)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def delta_layer(x, lp, i, cfg, faults):
    """The gated delta-rule mixer over one whole sequence ``x [S, E]``."""
    S = x.shape[0]
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    lost = faults.get("zero_state_at")
    qkv = jax.nn.silu(short_conv(
        x @ _f32(lp["in_qkv"], i), _f32(lp["conv"], i, "taps"), lost))
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
    # the gain is PLAIN: ``w``, not ``1 + w``
    y = norm(o, _f32(lp["o_norm"], i), cfg["rms_norm_eps"],
             1.0 if faults.get("delta_norm_offset") else 0.0)
    z = (x @ _f32(lp["in_z"], i)).reshape(S, nv, dv)
    gate = (2.0 * jax.nn.sigmoid(z) if faults.get("delta_gate") == "2sigmoid"
            else jax.nn.silu(z))
    return (y * gate).reshape(S, nv * dv) @ _f32(lp["out_proj"], i)


def attention_layer(x, lp, i, cfg, pos, faults, block):
    """Gated GQA attention over one whole sequence, the scores a block of
    queries at a time."""
    S = x.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    width = D if faults.get("rope_all") else int(
        D * cfg.get("partial_rotary_factor", 1.0))
    inv_freq = jnp.asarray(1.0 / cfg["rope_theta"] ** (
        np.arange(0, width, 2, dtype=np.float64) / width), jnp.float32)
    q = (x @ _f32(lp["wq"], i)).reshape(S, H, D)
    k = (x @ _f32(lp["wk"], i)).reshape(S, KV, D)
    v = (x @ _f32(lp["wv"], i)).reshape(S, KV, D)
    if faults.get("qk_norm", True):
        q = norm(q, _f32(lp["q_norm"], i), eps)
        k = norm(k, _f32(lp["k_norm"], i), eps)
    # (the half-split convention over the first ``width`` dims, no factor)
    q, k = rope(q, pos, inv_freq, 1.0), rope(k, pos, inv_freq, 1.0)
    k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
    out = []
    for lo in range(0, S, block):
        hi = min(lo + block, S)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) * D ** -0.5
        s = jnp.where((pos[lo:hi, None] >= pos[None, :])[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(out, axis=0)                            # [S, H, D]
    gate = jax.nn.sigmoid(x @ _f32(lp["attn_gate"], i)).reshape(S, H, D)
    if faults.get("gate_per_head"):
        gate = jnp.broadcast_to(
            jnp.mean(gate, axis=-1, keepdims=True), gate.shape)
    return (o * gate).reshape(S, H * D) @ _f32(lp["wo"], i)


def expert_layer(x, lp, i, cfg, faults, held=None, choice=None):
    """The softmax router over all the experts, the sum over those held
    here, and the gated shared expert.  Returns ``(out, router record)``."""
    k = cfg["num_experts_per_tok"]
    p = jax.nn.softmax(x @ _f32(lp["router"], i), axis=-1)      # [S, X]
    p_own, own = jax.lax.top_k(p, k)
    used = own
    if choice is not None:
        choice = jnp.asarray(choice)
        used = jnp.where(choice[:, :1] >= 0, choice, own)
    p_used = jnp.take_along_axis(p, used, axis=-1)
    w = p_used
    if cfg.get("norm_topk_prob", True) and faults.get("renormalize", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    X_held = lp["experts"]["w_gate"]["weight"].shape[1]
    lo, hi = held or cfg.get("held_experts") or (0, X_held)
    out = jnp.zeros_like(x)
    if faults.get("shared", True) and "shared" in lp:
        out = swiglu(x, *(_f32(lp["shared"][n], i)
                          for n in ("w_gate", "w_up", "w_down")))
        if "shared_gate" in lp and faults.get("shared_gate", True):
            out = out * jax.nn.sigmoid(x @ _f32(lp["shared_gate"], i))
    drop = faults.get("drop_expert")
    # the tree's expert ``e - lo0`` is published expert ``e``, ``lo0`` the
    # first the tree holds (``cfg["held_experts"]``, else 0)
    lo0 = (cfg.get("held_experts") or (0, X_held))[0]

    def one(acc, e):
        def w_of(name):
            leaf = lp["experts"][name]
            wt = jnp.asarray(leaf["weight"][i])[e - lo0].astype(jnp.float32)
            if "scale" in leaf:
                wt = wt * jnp.asarray(leaf["scale"][i])[e - lo0].astype(
                    jnp.float32)
            return wt

        w_e = jnp.sum(jnp.where(used == e, w, 0.0), axis=-1)       # [S]
        if drop is not None and drop != "all":
            w_e = jnp.where(e == drop, 0.0, w_e)
        y = swiglu(x, w_of("w_gate"), w_of("w_up"), w_of("w_down"))
        return acc + w_e[:, None] * y, None

    if drop != "all":
        out, _ = jax.lax.scan(one, out, jnp.arange(lo, hi))
    return out, {"own": own, "p_own": p_own, "used": used, "p_used": p_used}


def mixer(h, lp, i, cfg, pos, attn, faults, block=256):
    """The first half of a block: ``h + Mixer(n_a(h))``, ``attn`` an
    attention layer (else the delta rule)."""
    x = norm(h, _f32(lp["attn_norm"], i), cfg["rms_norm_eps"])
    if attn:
        return h + attention_layer(x, lp, i, cfg, pos, faults, block)
    return h + delta_layer(x, lp, i, cfg, faults)


def experts(h, lp, i, cfg, faults, held=None, choice=None):
    """The second half: ``(h + MoE(n_c(h)), router record)``."""
    x = norm(h, _f32(lp["mlp_norm"], i), cfg["rms_norm_eps"])
    y, rec = expert_layer(x, lp, i, cfg, faults, held, choice)
    return h + y, rec


def kinds(cfg):
    """``layer_homes``'s view of this configuration: the mixer of every
    layer (no leading dense layer: every layer's feed-forward is experts)."""
    every = cfg.get("full_attention_interval", 4)
    return {"layer_types": [
        "attn" if (l + 1) % every == 0 else "deltanet"
        for l in range(cfg["num_hidden_layers"])], "num_dense_layers": 0}


def forward(params, cfg, tokens, rows=None, layers=None, h=None, head=True,
            block=256, held=None, choices=None, return_router=False,
            **faults):
    """Logits ``[S, vocab]`` (``[len(rows), vocab]`` with ``rows``) of one
    sequence ``tokens [S]``; ``cfg`` has the Hugging Face keys of the
    configuration's JSON file.

    ``layers=(lo, hi)`` runs layers lo..hi-1 only: from the embedding if
    ``h`` is None, else from the hidden state ``h [S, E]``; ``head=False``
    returns the hidden state instead of logits (for the next block).
    ``held``, ``choices``, ``return_router``: the module's docstring."""
    view = kinds(cfg)
    homes = layer_homes(view)
    lo, hi = layers or (0, cfg["num_hidden_layers"])
    pos = jnp.arange(tokens.shape[0])
    router = []
    with jax.default_matmul_precision("highest"):
        if h is None:
            emb = params["embed"]
            h = jnp.asarray(emb["weight"], jnp.float32)[tokens]
            if "embed_scale" in emb:
                h = h * jnp.asarray(emb["embed_scale"], jnp.float32)[tokens]
        for l in range(lo, hi):
            key, i = homes[l]
            h = mixer(h, params[key], i, cfg, pos,
                      view["layer_types"][l] == "attn", faults, block)
            h, rec = experts(h, params[key], i, cfg, faults, held,
                             None if choices is None else choices[l])
            router.append(rec)
        out = h
        if head:
            if rows is not None:
                h = h[jnp.asarray(rows)]
            out = logits(h, params, cfg)
    if return_router:
        return out, {k: jnp.stack([r[k] for r in router])
                     for k in router[0]}
    return out


def logits(h, params, cfg):
    """``n(h; w_f) W_head`` of hidden rows ``h [n, E]``."""
    h = norm(h, jnp.asarray(params["final_norm"]["weight"], jnp.float32),
             cfg["rms_norm_eps"])
    head_p = params["lm_head"]
    w = jnp.asarray(head_p["weight"], jnp.float32)
    if "scale" in head_p:
        w = w * jnp.asarray(head_p["scale"], jnp.float32)
    return h @ w
