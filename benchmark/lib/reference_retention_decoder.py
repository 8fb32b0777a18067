"""The plain reference of ``brumby-14b-int8``: a decoder whose every token
mixer is POWER RETENTION of degree 2 (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239; the Brumby-14B-Base release), in
straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: the QUADRATIC form straight from
the definition, over the whole sequence.  No ``phi``, no state, no chunks, no
cache, no batching, no kernel, no quantisation.

Equations (``eps`` = rms_norm_eps; E hidden; H / KVH heads of width D; query
head h reads kv head j = h // (H / KVH); p = 2):
  h0 = Emb[tokens]
  per layer:
    n = RMSNorm_operator(h)
    q, k, v = n W_q, n W_k, n W_v       [S, H, D], [S, KVH, D], [S, KVH, D]
    q, k = RMSNorm_q(q), RMSNorm_k(k) over D (weights [D])
    q, k = RoPE(q), RoPE(k): rotate-half over all of D, theta rope_theta
    log g = logsigmoid(n W_g + b_g)                       [S, KVH]
    a_ts = exp(sum_{r=s+1..t} log g_r[j]) * ((q_t[h] . k_s[j]) / sqrt(D)) ** p
           for s <= t, else 0           (every weight is non-negative)
    y_t[h] = sum_s a_ts v_s[j] / (sum_s a_ts + 1e-6)
    h = h + concat_h(y[h]) W_o
    x = RMSNorm_ffn(h);  h = h + (silu(x W_gate) * (x W_up)) W_down
  logits = RMSNorm_final(h) W_head      (untied)

Departures from the published description, each also in the configuration
file's ``assumed``:
- the catalog row's keys carry the widths and no key of the mixer: the
  degree (2), the gate a kv head with a bias, the per-head q/k norms and
  rope kept from the Qwen3-14B block, the scale ``1 / sqrt(D)`` inside the
  power and the ``eps`` of the normaliser are this repo's reading of the
  paper and the release;
- the release's inference code keeps K and V until a switch-over length and
  the state after it; the function is the same, and this is it, in the form
  that has neither;
- weights are the PROGRAM's parameter tree (``models/llama.py::init_params``:
  one stack ``run00`` of all layers), read as float32, an int8 leaf times its
  scale: the served quantisation is shared by both sides, so a comparison
  shows the program's bf16 activations, its state pool, its chunked form and
  its kernel, and not the quantisation;
- the scores are computed in BLOCKS of queries (``block`` rows at a time,
  every key at once) and a layer at a time, so that 8k tokens at published
  widths fit a chip: [H, block, S] float32 is 1.3 GB at 1,024 x 8,192;
- ``forward(..., layers=(lo, hi), h=...)`` runs a block of layers from a
  hidden state (the blocks chained give the full forward);
- the faults a tolerance must catch, each off by default.  Most are
  properties of a STATE, which this form does not have, and are written as
  what they do to the weights ``a_ts``; one cannot be:
  ``state_bf16``: the state rounded to bfloat16 after every step.  Only a
  state can be rounded, so THIS FAULT ALONE runs the recurrence
  (``retention_by_state``: ``S_t[i, j, c] = g_t S_{t-1} + k_i k_j v_c``, the
  whole ``[D, D, D]`` tensor a kv head with nothing packed, ``y_t[c] =
  sum_ij q_i q_j S_t[i, j, c] / D`` over the float32 normaliser), a token at
  a time, ``S`` cast to bfloat16 and back after every update;
  ``gate=False``: g = 1, nothing decays;
  ``normaliser=False``: y = sum_s a_ts v_s, undivided;
  ``cross_sqrt2=False``: the cross products of ``phi`` without their sqrt 2,
  i.e. ``(q . k) ** 2`` replaced by ``(sum_i q_i^2 k_i^2 + (q . k) ** 2) /
  2``;
  ``zero_state_at``: a position at which every layer forgets what came
  before (a state zeroed at a chunk boundary): ``a_ts = 0`` for ``s <
  zero_state_at <= t``.
"""

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


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


def retention_by_state(q, k, v, log_g, state_dtype=jnp.bfloat16):
    """The ``state_bf16`` fault: the same function as a recurrence over an
    unpacked state ``[KVH, D, D, D]`` held in ``state_dtype`` between steps
    (the normaliser stays float32)."""
    S, H, D = q.shape
    KVH = k.shape[1]

    def step(carry, x):
        St, Zt = carry
        qt, kt, vt, lg = x
        g = jnp.exp(lg)
        St = g[:, None, None, None] * St.astype(jnp.float32) + (
            kt[:, :, None, None] * kt[:, None, :, None] * vt[:, None, None])
        St = St.astype(state_dtype)
        Zt = g[:, None, None] * Zt + kt[:, :, None] * kt[:, None, :]
        qg = qt.reshape(KVH, H // KVH, D)
        num = jnp.einsum("jgi,jgk,jikc->jgc", qg, qg,
                         St.astype(jnp.float32)) / D
        den = jnp.einsum("jgi,jgk,jik->jg", qg, qg, Zt) / D
        return (St, Zt), (num / (den[..., None] + EPS)).reshape(H, D)

    zeros = (jnp.zeros((KVH, D, D, D), state_dtype),
             jnp.zeros((KVH, D, D), jnp.float32))
    return jax.lax.scan(step, zeros, (q, k, v, log_g))[1]


def retention(q, k, v, log_g, block=1024, normaliser=True, cross_sqrt2=True,
              zero_state_at=None, state_bf16=False):
    """``y [S, H, D]`` from the definition: ``q [S, H, D]``, ``k, v [S, KVH,
    D]``, ``log_g [S, KVH]``, a block of queries at a time."""
    if state_bf16:
        return retention_by_state(q, k, v, log_g)
    S, H, D = q.shape
    KVH = k.shape[1]
    G = jnp.cumsum(log_g, axis=0)                               # [S, KVH]
    pos = jnp.arange(S)
    out = []
    for lo in range(0, S, block):
        t = pos[lo:lo + block]
        qb = q[lo:lo + block].reshape(-1, KVH, H // KVH, D)
        dots = jnp.einsum("tjgd,sjd->jgts", qb, k)
        sc = dots ** 2
        if not cross_sqrt2:
            sq = jnp.einsum("tjgd,sjd->jgts", qb * qb, k * k)
            sc = (sq + sc) / 2
        sc = sc / D
        keep = t[:, None] >= pos[None, :]
        if zero_state_at is not None:
            keep &= ~((pos[None, :] < zero_state_at)
                      & (t[:, None] >= zero_state_at))
        dec = jnp.exp(jnp.where(
            keep[None], G[lo:lo + block].T[:, :, None] - G.T[:, None, :],
            -jnp.inf))                                          # [KVH, t, s]
        a = sc * dec[:, None]                                   # [j, g, t, s]
        den = jnp.sum(a, axis=-1)                               # [j, g, t]
        num = jnp.einsum("jgts,sjd->tjgd", a, v)
        if normaliser:
            num = num / (den.transpose(2, 0, 1)[..., None] + EPS)
        out.append(num.reshape(-1, H, D))
    return jnp.concatenate(out, axis=0)


def _layer(h, lp, i, cfg, pos, gate, **faults):
    """One layer; ``lp`` is the stack that holds it, ``i`` its index there."""
    S, E = h.shape
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg.get("head_dim") or E // H
    eps = cfg["rms_norm_eps"]

    n = rms_norm(h, _f32(lp["attn_norm"], i), eps)
    q = (n @ _f32(lp["wq"], i)).reshape(S, H, D)
    k = (n @ _f32(lp["wk"], i)).reshape(S, KVH, D)
    v = (n @ _f32(lp["wv"], i)).reshape(S, KVH, D)
    q = rms_norm(q, _f32(lp["q_norm"], i), eps)
    k = rms_norm(k, _f32(lp["k_norm"], i), eps)
    q = rope_half(q, pos, cfg["rope_theta"])
    k = rope_half(k, pos, cfg["rope_theta"])
    log_g = jax.nn.log_sigmoid(
        n @ _f32(lp["g_proj"], i)
        + jnp.asarray(lp["g_bias"]["bias"][i], jnp.float32))
    if not gate:
        log_g = jnp.zeros_like(log_g)
    y = retention(q, k, v, log_g, **faults)
    h = h + y.reshape(S, H * D) @ _f32(lp["wo"], i)

    x = rms_norm(h, _f32(lp["mlp_norm"], i), eps)
    return h + (jax.nn.silu(x @ _f32(lp["w_gate"], i)) * (
        x @ _f32(lp["w_up"], i))) @ _f32(lp["w_down"], i)


def forward(params, cfg, tokens, layers=None, h=None, head=True, gate=True,
            rows=None, **faults):
    """Logits of one sequence ``tokens`` [S] (``[S, vocab]``, or the rows
    ``rows`` of it: the head over 8k tokens is 5 GB); ``cfg`` has the Hugging
    Face keys of the configuration's JSON file.

    ``layers=(lo, hi)`` runs layers lo..hi-1 only: from the embedding if
    ``h`` is None, else from the hidden state ``h`` [S, E]; ``head=False``
    returns the hidden state instead of logits (for the next block).
    ``faults``: ``retention``'s keyword arguments."""
    lo, hi = layers or (0, cfg["num_hidden_layers"])
    pos = jnp.arange(tokens.shape[0])
    with jax.default_matmul_precision("highest"):
        if h is None:
            h = _f32(params["embed"])[tokens]
        for layer in range(lo, hi):
            # every layer is of one kind: one stack, in layer order
            h = _layer(h, params["run00"], layer, cfg, pos, gate, **faults)
        if not head:
            return h
        if rows is not None:
            h = h[jnp.asarray(rows)]
        h = rms_norm(h, jnp.asarray(params["final_norm"]["weight"],
                                    jnp.float32), cfg["rms_norm_eps"])
        return h @ _f32(params["lm_head"])
