"""The plain reference of ``laguna-xs2-int8``: a decoder of SLIDING-WINDOW
attention layers beside FULL-attention layers, each kind with its own count of
query heads and its own rope, a sigmoid gate a head on the attention's output,
one dense SwiGLU layer and then routed experts behind a sigmoid router
(renormalised over the chosen, scaled) with one shared expert, of which this
rank holds some: straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``.  Whole-sequence attention with
the explicit causal and window masks, the experts as a loop.  No ring, no
page, no cache, no batching, no kernel, no quantisation.

Equations.  ``n(x; w) = x / rms(x) * w``, eps rms_norm_eps.  E hidden, D = 128
the head width, KV kv heads everywhere, H_t query heads by the layer's kind
(``num_attention_heads_per_layer``), W = sliding_window.
  h0 = Emb[tokens]
  a block:  x' = n(h; w_a);  h = h + Attn(x');  x'' = n(h; w_m);  h = h + FFN(x'')
  Attn:  q = x' W_q -> [S, H_t, D];  k = x' W_k, v = x' W_v -> [S, KV, D]; no bias
    a FULL layer (``full_attention``): rope on the FIRST 64 of the 128 dims
      (partial_rotary_factor 0.5: pairs (i, i + 32) of those 64), YaRN inverse
      frequencies over that width (theta 500,000, factor 64 over 4,096, beta
      64 / 1), cos and sin times attention_factor; the other 64 dims pass
    a SLIDING layer: plain rope on all 128 dims (pairs (i, i + 64)), theta
      10,000
    score_h[i, j] = q_h[i] . k_{h // (H_t / KV)}[j] / sqrt(D), kept where
      j <= i, and on a sliding layer where also i - j < W (W keys, the query's
      own among them)
    o_h = softmax(score_h) v;  g = sigmoid(x' W_g) -> [S, H_t];  o_h = g_h o_h
    Attn = concat_h(o_h) W_o
  FFN, a ``dense`` layer and every expert:  W_d(silu(x W_gate) * (x W_up))
  FFN, a ``sparse`` layer:  s = sigmoid(x'' W_r) over ALL n routed experts;
    idx = top-k(s);  w = moe_routed_scaling_factor * s[idx] / (sum s[idx] + 1e-6)
    FFN = sum_{j: idx_j held here} w_j Expert_idx_j(x'') + Shared(x'')
  logits = n(h; w_f) W_head      (untied)

ASSUMED readings of the published config (each also in the configuration
file's ``assumed``; the config states the sizes and none of these):
- ``gating: true`` is ONE value a head (the sibling Laguna-S-2.1 states
  ``gating: "per-head"``, and the published 33.4B parameters count out only
  with a gate of H_t values a layer: a gate a channel would make it 34.07B),
  taken from the branch's normed input through a sigmoid, with a projection
  of its own ``W_g [E, H_t]``;
- the router scores by sigmoid and renormalises over the chosen eight (the
  sibling states ``norm_topk_prob: true``; 256 experts / top-8 / 2.5 is that
  recipe), with NO selection bias (no key names one); the ``1e-6`` in the
  divisor is this repo's sigmoid router's;
- no q/k norm (no key names one);
- YaRN's ``attention_factor`` multiplies cos and sin, so it acts on the
  rotated dims only (Hugging Face's convention for a stated factor); the
  scores take no further factor;
- the window counts the query itself: ``sliding_window`` 512 keys at most;
- HELD EXPERTS: ``cfg["held_experts"] = [lo, hi)``: the parameter tree holds
  those experts of ``published_num_experts``; the router scores them all, and
  the layer's sum runs over the held ones (with the shared expert, which every
  rank computes): one expert-parallel rank's part of the layer, what this chip
  computes.  Without the key every expert is here;
- weights are the PROGRAM's parameter tree (``models/llama.py::init_params``),
  read as float32, an int8 leaf times its scale: the served quantisation is
  shared by both sides, so a comparison shows the program's bf16 activations,
  its rings and pages, its kernels and its split of the token axis, and not
  the quantisation;
- attention scores are computed in BLOCKS of queries (``block`` rows at a
  time, every key at once), so that two thousand tokens at 64 heads fit;
- ``forward(..., layers=(lo, hi), h=...)`` runs a block of layers from a
  hidden state (the blocks chained give the full forward); ``rows`` picks the
  positions whose logits come back;
- the faults a tolerance must catch, each off by default: ``no_window`` (a
  sliding layer attends every earlier token), ``window_off_by_one`` (W + 1
  keys), ``full_rotary`` (the full layers rotated over all 128 dims),
  ``one_rope`` (the sliding layers given the full layers' table, width and
  factor), ``drop_gate`` (no gate on the attention's output), ``drop_expert=e``
  (held expert ``e``'s part left out of every layer's sum; ``"all"``: every
  held expert's); and ``shared=False``, no fault: the shared expert left out,
  for the test that adds the ranks' routed parts and counts it once.
"""

import jax
import jax.numpy as jnp

from benchmark.lib.reference_hybrid_conv_moe_decoder import layer_homes
from benchmark.lib.reference_mla_moe_decoder import (
    _f32_expert, yarn_inv_freq,
)

KINDS = {"full_attention": "attn", "sliding_attention": "window"}


def norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _f32(leaf, i, key="weight"):
    w = jnp.asarray(leaf[key][i], jnp.float32)
    if "scale" in leaf:
        w = w * jnp.asarray(leaf["scale"][i], jnp.float32)
    return w


def glu(x, p, i, expert=None):
    def w(name):
        return (_f32(p[name], i) if expert is None
                else _f32_expert(p[name], i, expert))

    return (jax.nn.silu(x @ w("w_gate")) * (x @ w("w_up"))) @ w("w_down")


def rope_table(cfg, kind):
    """``(inverse frequencies, cos/sin factor)`` of a kind of layer, from
    ``rope_parameters[kind]``: the table's length is half the rotary width."""
    r = dict(cfg["rope_parameters"][kind])
    width = int(cfg["head_dim"] * r.get("partial_rotary_factor", 1))
    yarn = r.get("rope_type", "default") == "yarn"
    inv = yarn_inv_freq(width, r["rope_theta"], r if yarn else None)
    return jnp.asarray(inv), float(r.get("attention_factor", 1.0) if yarn
                                   else 1.0)


def rope(x, pos, inv_freq, factor):
    """Rotate the first ``2 * len(inv_freq)`` dims of ``x [S, heads, D]``,
    pairs ``(i, i + width / 2)``; the rest pass through."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq          # [S, w/2]
    cos, sin = jnp.cos(ang)[:, None] * factor, jnp.sin(ang)[:, None] * factor
    half = inv_freq.shape[0]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(x, lp, i, cfg, pos, kind, faults, block):
    """One attention layer over one whole sequence ``x [S, E]``, ``kind`` a
    key of ``rope_parameters``."""
    S = x.shape[0]
    D, KV = cfg["head_dim"], cfg["num_key_value_heads"]
    q = (x @ _f32(lp["wq"], i)).reshape(S, -1, D)
    H = q.shape[1]
    k = (x @ _f32(lp["wk"], i)).reshape(S, KV, D)
    v = (x @ _f32(lp["wv"], i)).reshape(S, KV, D)
    table = kind
    if kind == "sliding_attention" and faults.get("one_rope"):
        table = "full_attention"
    inv, factor = rope_table(cfg, table)
    if table == "full_attention" and faults.get("full_rotary"):
        r = dict(cfg["rope_parameters"][table], partial_rotary_factor=1)
        inv, _ = rope_table(dict(cfg, rope_parameters={table: r}), table)
    q, k = rope(q, pos, inv, factor), rope(k, pos, inv, factor)
    k, v = (jnp.repeat(a, H // KV, axis=1) for a in (k, v))
    W = None
    if kind == "sliding_attention" and not faults.get("no_window"):
        W = cfg["sliding_window"] + bool(faults.get("window_off_by_one"))
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
    if "attn_gate" in lp and not faults.get("drop_gate"):
        o = o * jax.nn.sigmoid(x @ _f32(lp["attn_gate"], i))[..., None]
    return o.reshape(S, H * D) @ _f32(lp["wo"], i)


def expert_layer(x, lp, i, cfg, faults):
    """The router over all the experts, the sum over those held here, and
    the shared expert."""
    s = jax.nn.sigmoid(x @ _f32(lp["router"], i))                  # [S, n]
    _, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    w = w * cfg.get("moe_routed_scaling_factor", 1.0)
    lo, hi = cfg.get("held_experts") or (
        0, lp["experts"]["w_gate"]["weight"].shape[1])
    out = glu(x, lp["shared"], i) if "shared" in lp else 0.0
    if faults.get("shared", True) is False:
        out = 0.0
    for e in range(lo, hi):                    # a loop over the held experts
        if faults.get("drop_expert") in (e, "all"):
            continue
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)        # [S]
        out = out + w_e[:, None] * glu(x, lp["experts"], i, e - lo)
    return out


def layer(h, lp, i, cfg, pos, kind, dense, faults, block=256):
    """One block: ``lp`` the stack that holds it, ``i`` its index there,
    ``kind`` its attention's (a key of ``rope_parameters``), ``dense`` a
    dense FFN (else the experts)."""
    eps = cfg["rms_norm_eps"]
    x = norm(h, _f32(lp["attn_norm"], i), eps)
    h = h + attention(x, lp, i, cfg, pos, kind, faults, block)
    x = norm(h, _f32(lp["mlp_norm"], i), eps)
    return h + (glu(x, lp, i) if dense else expert_layer(
        x, lp, i, cfg, faults))


def kinds(cfg):
    """``layer_homes``'s view of this configuration: the mixer of every
    layer under the program's names, and the count of leading dense layers."""
    mlp = cfg.get("mlp_layer_types") or []
    dense = 0
    while dense < len(mlp) and mlp[dense] == "dense":
        dense += 1
    return {"layer_types": [KINDS[t] for t in cfg["layer_types"]],
            "num_dense_layers": dense}


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
