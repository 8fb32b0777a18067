"""The plain reference of the benchmark's dense decoder configurations
(Qwen2, Mistral): the published forward pass in straightforward
``jax.numpy`` and float32, with no kernels, cache, batching or quantization.

Equations (Llama-family decoder, as published for both models):
  h0 = E[tokens]
  per layer:  x = RMSNorm(h) ; q,k,v = x Wq (+bq), x Wk (+bk), x Wv (+bv)
              q,k = RoPE(q), RoPE(k)   (rotate-half pairing, base rope_theta)
              a = softmax(q k^T / sqrt(d) + causal mask) v, grouped: query
                  head i reads kv head i // (H / KVH)
              h = h + a Wo ; x = RMSNorm(h)
              h = h + (silu(x Wgate) * (x Wup)) Wdown
  logits = RMSNorm(h) Wlm_head
Departures from the publications: none in the mathematics.  Weights are the
program's stacked float tree (``models/llama.py::init_params``), read as
float32; the served int8 weights and bf16 activations are the system's, not
the reference's.  The server exposes no logits (PERF.md, Open questions), so
on the chip the reference cannot be compared from outside; the CPU test in
``tests/benchmark`` compares it with the program's forward at a small size.
"""

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """x: [S, heads, d]; rotate-half pairing (i, i + d/2)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv          # [S, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params, cfg, tokens):
    """Logits [S, vocab] of one sequence ``tokens`` [S].  ``cfg`` has the
    Hugging Face keys of the configuration's JSON file."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)   # noqa: E731
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // H
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    mask = pos[:, None] >= pos[None, :]
    lay = params["layers"]
    with jax.default_matmul_precision("highest"):
        h = f32(params["embed"]["weight"])[tokens]
        for i in range(cfg["num_hidden_layers"]):
            def w(name, key="weight"):
                return f32(lay[name][key][i])
            x = rms_norm(h, w("attn_norm"), eps)
            q, k, v = x @ w("wq"), x @ w("wk"), x @ w("wv")
            if "bias" in lay["wq"]:
                q, k, v = (q + w("wq", "bias"), k + w("wk", "bias"),
                           v + w("wv", "bias"))
            q = rope(q.reshape(S, H, d), pos, theta)
            k = rope(k.reshape(S, KVH, d), pos, theta)
            v = v.reshape(S, KVH, d)
            k, v = (jnp.repeat(t, H // KVH, axis=1) for t in (k, v))
            s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(d))
            s = jnp.where(mask[None], s, -jnp.inf)
            a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
            h = h + a.reshape(S, H * d) @ w("wo")
            x = rms_norm(h, w("mlp_norm"), eps)
            h = h + (jax.nn.silu(x @ w("w_gate")) * (x @ w("w_up"))) @ w(
                "w_down")
        h = rms_norm(h, f32(params["final_norm"]["weight"]), eps)
        head = (f32(params["embed"]["weight"]).T
                if cfg.get("tie_word_embeddings")
                else f32(params["lm_head"]["weight"]))
        return h @ head
