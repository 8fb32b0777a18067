"""The plain reference of ``nemotron-3-super-120b-a12b-int8``: a decoder of
layers that are ONE branch each (``model_type`` nemotron_h): Mamba-2 mixers
(state space duality, arXiv:2405.21060), attention with no position encoding,
and routed experts that are ungated squared-ReLU MLPs in a latent narrower
than the model, behind a sigmoid-and-bias router, of which this rank holds
some; in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: the state space as the
token-by-token recurrence, attention with explicit scores, the experts as a
loop.  No chunked form, no cache, no batching, no kernel, no quantisation.
It imports nothing from ``helix_tpu``.

Equations.  ``n(x; w) = x / rms(x) * w``, eps ``norm_eps``.  E hidden.
  h0 = Emb[tokens]
  layer l of ``hybrid_override_pattern`` (the PUBLISHED numbering, one branch
  a layer):  h = h + Branch_l(n(h; w_l)).  After the last, logits = n(h; w_f)
  W_head (untied).
  "M", Mamba-2 (H = mamba_num_heads heads of P = mamba_head_dim; G = n_groups;
  N = ssm_state_size; K = conv_kernel taps):
    [z | xBC | dt] = u W_in, widths H P | H P + 2 G N | H, in that order
    xBC = silu(conv_K(xBC) + b): causal, depthwise, zeros before the start
    [x | B | C] = xBC at H P | G N | G N; x [H, P]; B, C [G, N]; head j reads
      group j // (H / G)
    dt = softplus(dt + dt_bias);  a = exp(dt * A),  A = -exp(A_log)
    h_t = a_t h_{t-1} + (dt_t x_t) B_t^T  ([P, N] a head, from 0)
    y_t = h_t C_t + D x_t
    Branch = W_out (g * GroupRMS(y * silu(z))): the gate BEFORE the norm, the
      norm over each group's H P / G channels, eps norm_eps
  "*", attention (Hq query heads over Hkv kv heads of d):
    q, k, v = u W_q, u W_k, u W_v;  NO rotary embedding;  causal softmax at
    d ** -0.5;  Branch = concat_h(a_h) W_o
  "E", experts:  s = sigmoid(u W_r) over ALL the published experts;
    idx = top-k(s + bias);  w = routed_scaling_factor * s[idx] / (sum s[idx]
    + 1e-6);  v = u W_fc1;  r = sum_{j: idx_j held here} w_j W_down,j
    relu(W_up,j v) ** 2;  Branch = r W_fc2 + W_sd relu(W_su u) ** 2
  "-", a dense MLP:  Branch = W_down relu(W_up u) ** 2

Readings of the published config that it does not spell out, each also in the
configuration file's ``assumed``:
- attention applies no rotary embedding (``rope_theta`` and
  ``partial_rotary_factor`` are read by no layer);
- the gate ``silu(z)`` multiplies ``y`` BEFORE the grouped norm, and the norm
  runs over each of the ``n_groups`` groups' channels apart;
- the in-projection's order is z | x | B | C | dt;
- ``B`` and ``C`` are shared by the ``H / G`` heads of a group;
- the router's divisor carries ``1e-6`` (this repo's sigmoid router's; the
  modeling file's 1e-20 differs from it by 1e-7 of a weight at 22 scores of a
  half), and the weights leave out the selection bias;
- the latent's two projections have no bias and the router and the shared
  expert read the un-projected input;
- the multi-token-prediction module is not run;
- HELD EXPERTS: ``cfg["held_experts"] = [lo, hi)``: the parameter tree holds
  those experts of ``published_n_routed_experts``; the router scores them all,
  and the layer's sum runs over the held ones (with the shared expert, which
  every rank computes).  Without the key every expert is here;
- weights are the PROGRAM's parameter tree (``models/llama.py::init_params``:
  a stack a run of BLOCKS, a block a mixer layer and the feed-forward layer
  behind it if one follows; ``homes`` finds a published layer's weights; the
  in-projection is stored as its three parts), read as float32, an int8 leaf
  times its scale: the served quantisation is shared by both sides;
- attention scores are computed in BLOCKS of queries;
- ``forward(..., layers=(lo, hi), h=...)`` runs published layers lo..hi-1 from
  a hidden state; ``rows`` picks the positions whose logits come back;
- the faults a tolerance must catch, each off by default: ``decay=False`` (a =
  1), ``softplus=False`` (dt not through softplus), ``skip=False`` (no ``D
  x``), ``gate_after_norm=True``, ``act="relu"`` (relu2 as relu),
  ``scaling=False`` (the routed scaling dropped), ``bias_in_weights=True``
  (the selection bias counted into the weights), ``rope_theta=t`` (rope
  applied to q and k at that base), ``zero_state_at=t`` (every Mamba-2 layer
  forgets, ``h`` and conv tail, at that position: a state lost between two
  chunks), ``drop_expert=e`` (held expert ``e``'s part left out of every
  layer's sum; ``"all"``: every held expert's), ``top_k=k`` (another count of
  choices), ``state_bf16`` (``h`` rounded to bfloat16 after every token); and
  ``shared=False``, no fault: the shared expert left out, for the test that
  adds the ranks' routed parts and counts it once, with ``latent_out=False``:
  the routed sum handed back BEFORE ``W_fc2``.
"""

import jax
import jax.numpy as jnp

from benchmark.lib.reference_hybrid_conv_moe_decoder import (
    layer_homes, short_conv,
)


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _f32(leaf, i, key="weight"):
    w = jnp.asarray(leaf[key][i], jnp.float32)
    if "scale" in leaf and key == "weight":
        w = w * jnp.asarray(leaf["scale"][i], jnp.float32)
    return w


def blocks(pattern):
    """The published layers as the program's blocks: ``[(mixer layer, its
    kind, feed-forward layer or None, its kind)]``."""
    out = []
    for l, ch in enumerate(pattern):
        if ch in "M*":
            out.append([l, ch, None, None])
        else:
            assert out and out[-1][2] is None, (l, ch)
            out[-1][2:] = [l, ch]
    return out


def homes(pattern):
    """``{published layer: (stack key, index in the stack)}``: a block's two
    layers share a home, as the program lays its tree out."""
    bl = blocks(pattern)
    at = layer_homes({"layer_types": [(m, f) for _, m, _, f in bl]})
    out = {}
    for (lm, _, lf, _), home in zip(bl, at):
        out[lm] = home
        if lf is not None:
            out[lf] = home
    return out


def act_of(cfg, faults):
    name = faults.get("act") or cfg.get("mlp_hidden_act", "relu2")
    return {"relu2": lambda x: jnp.square(jax.nn.relu(x)),
            "relu": jax.nn.relu, "silu": jax.nn.silu}[name]


def mamba_layer(u, lp, i, cfg, faults):
    """The Mamba-2 mixer over one whole sequence ``u [S, E]``."""
    S = u.shape[0]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    lost = faults.get("zero_state_at")
    z = u @ _f32(lp["in_z"], i)
    xbc = short_conv(u @ _f32(lp["in_xbc"], i), _f32(lp["conv"], i, "taps"),
                     lost)
    xbc = jax.nn.silu(xbc + _f32(lp["conv"], i, "bias"))
    x, Bm, Cm = jnp.split(xbc, [H * P, H * P + G * N], axis=-1)
    x = x.reshape(S, H, P)
    Bm, Cm = (jnp.repeat(a.reshape(S, G, N), H // G, axis=1)
              for a in (Bm, Cm))                                   # [S, H, N]
    dt = u @ _f32(lp["in_dt"], i) + jnp.asarray(
        lp["dt_bias"]["bias"][i], jnp.float32)
    if faults.get("softplus", True):
        dt = jax.nn.softplus(dt)
    a = jnp.exp(-jnp.exp(jnp.asarray(lp["A_log"]["bias"][i], jnp.float32))
                * dt)
    if not faults.get("decay", True):
        a = jnp.ones_like(a)

    def token(h, xs):
        x_t, B_t, C_t, a_t, dt_t, t = xs
        if lost is not None:
            h = jnp.where(t == lost, 0.0, h)
        h = a_t[:, None, None] * h + (
            (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        if faults.get("state_bf16"):
            # (not a cast there and back: the TPU's compiler takes such a
            # pair out as excess precision it is allowed to keep)
            h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
        return h, jnp.einsum("hpn,hn->hp", h, C_t)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                        (x, Bm, Cm, a, dt, jnp.arange(S)))
    if faults.get("skip", True):
        y = y + jnp.asarray(lp["D"]["bias"][i], jnp.float32)[:, None] * x
    y = y.reshape(S, H * P)
    gate = jax.nn.silu(z)

    def group_norm(v):
        v = v.reshape(S, G, -1)
        v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                              + cfg["norm_eps"])
        return v.reshape(S, H * P) * _f32(lp["o_norm"], i)

    y = (group_norm(y) * gate if faults.get("gate_after_norm")
         else group_norm(y * gate))
    return y @ _f32(lp["out_proj"], i)


def rope(x, pos, theta):
    """Rotate-half rope over a whole head (the fault only)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_layer(u, lp, i, cfg, pos, faults, block):
    """Attention with no position encoding, the scores a block of queries at
    a time."""
    S = u.shape[0]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    q = (u @ _f32(lp["wq"], i)).reshape(S, Hq, d)
    k = (u @ _f32(lp["wk"], i)).reshape(S, Hkv, d)
    v = (u @ _f32(lp["wv"], i)).reshape(S, Hkv, d)
    if faults.get("rope_theta"):
        q = rope(q, pos, faults["rope_theta"])
        k = rope(k, pos, faults["rope_theta"])
    k, v = (jnp.repeat(a, Hq // Hkv, axis=1) for a in (k, v))
    out = []
    for lo in range(0, S, block):
        hi = min(lo + block, S)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) * d ** -0.5
        s = jnp.where((pos[lo:hi, None] >= pos[None, :])[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(out, axis=0).reshape(S, Hq * d) @ _f32(lp["wo"], i)


def _expert(leaf, i, e):
    w = jnp.asarray(leaf["weight"][i])[e].astype(jnp.float32)
    if "scale" in leaf:
        w = w * jnp.asarray(leaf["scale"][i], jnp.float32)[e]
    return w


def expert_layer(u, lp, i, cfg, faults):
    """The router over all the experts, the sum over those held here in the
    latent, and the shared expert."""
    act = act_of(cfg, faults)
    s = jax.nn.sigmoid(u @ _f32(lp["router"], i))                  # [S, n]
    bias = jnp.asarray(lp["expert_bias"]["bias"][i], jnp.float32)
    _, idx = jax.lax.top_k(
        s + bias, faults.get("top_k") or cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(
        s + bias if faults.get("bias_in_weights") else s, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    if faults.get("scaling", True):
        w = w * cfg.get("routed_scaling_factor", 1.0)
    lo, hi = cfg.get("held_experts") or (
        0, lp["experts"]["w_up"]["weight"].shape[1])
    v = u @ _f32(lp["fc1"], i) if "fc1" in lp else u
    drop = faults.get("drop_expert")

    def one(e, r):                             # a loop over the held experts
        w_e = jnp.sum(jnp.where(idx == e + lo, w, 0.0), axis=-1)   # [S]
        y = act(v @ _expert(lp["experts"]["w_up"], i, e)) @ _expert(
            lp["experts"]["w_down"], i, e)
        keep = True if drop is None or drop == "all" else e + lo != drop
        return r + jnp.where(keep, w_e, 0.0)[:, None] * y

    r = jnp.zeros_like(v)
    if drop != "all":
        r = jax.lax.fori_loop(0, hi - lo, one, r)
    if faults.get("latent_out", True) and "fc2" in lp:
        r = r @ _f32(lp["fc2"], i)
    if "shared" in lp and faults.get("shared", True):
        r = r + act(u @ _f32(lp["shared"]["w_up"], i)) @ _f32(
            lp["shared"]["w_down"], i)
    return r


def layer(h, lp, i, kind, cfg, pos, faults, block=256):
    """Published layer of ``kind`` (a character of the pattern): ``lp`` the
    stack that holds its block, ``i`` its index there."""
    eps = cfg["norm_eps"]
    if kind in "M*":
        u = norm(h, _f32(lp["attn_norm"], i), eps)
        if kind == "M":
            return h + mamba_layer(u, lp, i, cfg, faults)
        return h + attention_layer(u, lp, i, cfg, pos, faults, block)
    u = norm(h, _f32(lp["mlp_norm"], i), eps)
    if kind == "E":
        return h + expert_layer(u, lp, i, cfg, faults)
    return h + act_of(cfg, faults)(u @ _f32(lp["w_up"], i)) @ _f32(
        lp["w_down"], i)


def forward(params, cfg, tokens, rows=None, layers=None, h=None, head=True,
            block=256, **faults):
    """Logits ``[S, vocab]`` (``[len(rows), vocab]`` with ``rows``) of one
    sequence ``tokens [S]``; ``cfg`` has the Hugging Face keys of the
    configuration's JSON file.

    ``layers=(lo, hi)`` runs PUBLISHED layers lo..hi-1 only: from the
    embedding if ``h`` is None, else from the hidden state ``h [S, E]``;
    ``head=False`` returns the hidden state instead of logits (for the next
    block)."""
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["num_hidden_layers"]
    at = homes(pattern)
    lo, hi = layers or (0, len(pattern))
    pos = jnp.arange(tokens.shape[0])
    with jax.default_matmul_precision("highest"):
        if h is None:
            emb = params["embed"]
            h = jnp.asarray(emb["weight"], jnp.float32)[tokens]
            if "embed_scale" in emb:
                h = h * jnp.asarray(emb["embed_scale"], jnp.float32)[tokens]
        for l in range(lo, hi):
            key, i = at[l]
            h = layer(h, params[key], i, pattern[l], cfg, pos, faults, block)
        if not head:
            return h
        if rows is not None:
            h = h[jnp.asarray(rows)]
        h = norm(h, jnp.asarray(params["final_norm"]["weight"], jnp.float32),
                 cfg["norm_eps"])
        head_p = params["lm_head"]
        w = jnp.asarray(head_p["weight"], jnp.float32)
        if "scale" in head_p:
            w = w * jnp.asarray(head_p["scale"], jnp.float32)
        return h @ w
