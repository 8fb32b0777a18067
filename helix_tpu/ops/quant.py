"""Weight-only int8 quantization.

Fits Llama-3-8B (16.06 GB bf16 — over a v5e chip's 16 GiB HBM) on a single
chip and halves weight HBM traffic, which is the decode bottleneck.  The
reference reaches the same goal by passing ``--quantization`` flags to vLLM
containers; here it is a pytree transform:

- per-output-channel absmax scales (fp32), symmetric, no zero point;
- matmul runs ``x_bf16 @ cast(w_int8 -> bf16)`` then scales the output —
  the cast happens in VMEM after the (halved) HBM fetch, so bandwidth wins
  are kept while the MXU stays in its well-tuned bf16 path;
- norms/biases stay bf16 (negligible bytes, precision-critical).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


# Floor on KV quantization scales: keeps all-zero (never-written) page
# slots exactly representable and the dequant multiply finite.
KV_SCALE_EPS = 1e-8


def quantize_kv(x: jax.Array):
    """Symmetric int8 KV quantization with per-(token-slot, kv-head)
    fp32 scales — absmax over the trailing head_dim axis only.

    Per-slot (not whole-page) granularity is what makes incremental
    decode writes safe: appending a token never has to requantize the
    page's existing slots against a new scale, it just writes its own
    ``[KVH, D]`` codes plus a ``[KVH]`` scale row.

    x: ``[..., KVH, D]`` -> (int8 ``[..., KVH, D]``, f32 ``[..., KVH]``).
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(absmax / 127.0, KV_SCALE_EPS)
    q = jnp.clip(
        jnp.round(xf / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv` (broadcasts the per-head scale over
    head_dim)."""
    out = q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
    return out.astype(dtype)


def pack_scale_pages(scale: jax.Array) -> jax.Array:
    """Per-page scale rows ``[..., P, KVH]`` -> the pools' lane-dense
    page rows ``[..., KVH*P]`` (head-major inside a page).

    On the TPU an f32 array's minor axis is padded to 128 lanes, so a
    pool with ``KVH`` (4 or 8) minor would cost 16-32x its nominal HBM
    and the kernel could not slice it; ``KVH*P`` is 64-128 wide.  Pages
    on the host and on the wire keep the ``[P, KVH]`` form."""
    *lead, P, KVH = scale.shape
    return jnp.swapaxes(scale, -1, -2).reshape(*lead, KVH * P)


def unpack_scale_pages(rows: jax.Array, page_size: int) -> jax.Array:
    """Inverse of :func:`pack_scale_pages`: ``[..., KVH*P]`` -> ``[..., P,
    KVH]``."""
    *lead, W = rows.shape
    return jnp.swapaxes(
        rows.reshape(*lead, W // page_size, page_size), -1, -2
    )


def quantize_tensor(w: jax.Array):
    """Symmetric int8, per-output-channel (last axis) scales.

    Stacked-layer weights ``[L, in, out]`` keep independent scales per layer
    (reduce over the contraction axes only, never the leading layer axis).
    Returns {"weight": int8 array, "scale": f32}.
    """
    wf = w.astype(jnp.float32)
    # reduce ONLY the contraction (input) axis: leading axes are batch
    # dims (stacked layers, stacked experts) that must keep independent
    # scales — reducing over experts would let one loud expert crush the
    # quantization levels of the others
    reduce_axes = (w.ndim - 2,)
    absmax = jnp.max(jnp.abs(wf), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"weight": q, "scale": scale.astype(jnp.float32)}


def quantize_embedding(w: jax.Array):
    """Embedding table: int8 with per-row scales (lookup then rescale)."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"weight": q, "embed_scale": scale.astype(jnp.float32)}


def _map_matmul_weights(tree, quantize, other, aux=None, path=()):
    """Rebuild a model tree with ``quantize(v, aux, embed)`` (a dict of
    leaves) replacing every matmul weight and ``other(v, aux)`` every
    other leaf.  ``aux`` is an optional parallel tree (logical axes)."""
    if not isinstance(tree, dict):
        return other(tree, aux)
    out = {}
    for k, v in tree.items():
        a = None if aux is None else aux[k]
        if (
            k == "weight"
            and hasattr(v, "ndim")
            and v.ndim >= 2
            and not any("norm" in p for p in path)
        ):
            out.update(quantize(v, a, bool(path) and path[-1] == "embed"))
        else:
            out[k] = _map_matmul_weights(v, quantize, other, a, path + (k,))
    return out


def quantize_params(params: Any) -> Any:
    """Quantize every matmul weight in a model tree; embedding rows get
    per-row scales (lookup then rescale)."""
    return _map_matmul_weights(
        params,
        lambda v, _, embed: (
            quantize_embedding(v) if embed else quantize_tensor(v)
        ),
        lambda v, _: v,
    )


def quantize_params_streamed(params: Any, place, aux: Any = None) -> Any:
    """``quantize_params`` for a HOST tree on its way to the device:
    ``place(leaf, aux_leaf)`` puts one leaf on the device(s), and each
    matmul weight is quantized there by its own donated jit before the
    next is placed.  The device then never holds more than one tensor in
    its source dtype beside the int8 tree — what lets a 7-8B bf16
    checkpoint load as int8 onto one 16 GB chip."""
    q_embed = jax.jit(quantize_embedding, donate_argnums=0)
    q_dense = jax.jit(quantize_tensor, donate_argnums=0)
    return _map_matmul_weights(
        params,
        lambda v, a, embed: (q_embed if embed else q_dense)(place(v, a)),
        place,
        aux,
    )


def quantized_logical_axes(axes_tree: Any) -> Any:
    """Transform a logical-axes tree matching the *unquantized* param layout
    (``models.llama.param_logical_axes``) into one matching
    ``quantize_params``' output layout, so int8 trees can be sharded with
    ``parallel.sharding.shard_params`` / used as jit out_shardings.

    Mirrors the walk in ``quantize_params``: every quantized ``weight``
    gains a ``scale`` whose reduced (contraction) axes are replicated and
    whose output-channel axis keeps the weight's sharding — the dequant
    multiply then needs no extra collectives.  Embeddings gain a per-row
    ``embed_scale`` sharded like the vocab axis.
    """

    def walk(tree, path=()):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                if (
                    k == "weight"
                    and isinstance(v, tuple)
                    and len(v) >= 2
                    and not any("norm" in p for p in path)
                ):
                    out["weight"] = v
                    if path and path[-1] == "embed":
                        out["embed_scale"] = (v[0], None)
                    else:
                        out["scale"] = tuple(
                            a if i == len(v) - 1 else None
                            for i, a in enumerate(v)
                        )
                else:
                    out[k] = walk(v, path + (k,))
            return out
        return tree

    return walk(axes_tree)


def maybe_dequant_dense(x, p: dict, adapter_ids=None, compute_dtype=None):
    """Dense through a weight dict {weight[, scale, bias, lora_a/lora_b,
    lora_pool_a/lora_pool_b/lora_pool_scale]}.

    Handles int8 weight-only dequant, a single grafted LoRA adapter
    (``helix_tpu.training.lora`` — the merge-at-apply fallback), and the
    batched multi-LoRA pool (``helix_tpu.engine.adapters``) in one place
    so every projection in every model family composes with all three.

    The pool path is BGMV-style: ``lora_pool_a [N, in, r]`` /
    ``lora_pool_b [N, r, out]`` stack N adapter slots (slot 0 = the
    zero identity adapter) and ``adapter_ids [..., S]`` names each
    token's slot; the per-slot low-rank products are masked by the
    token's one-hot slot selection BEFORE the B matmul, so summing over
    N recovers exactly ``scale[g] * (x_t @ A[g]) @ B[g]`` per token —
    two dense rank-sized einsums on the MXU, no per-token weight
    gathers.  Rows at slot 0 contribute an exact ``+0.0``, keeping
    greedy outputs for adapter-free traffic bit-identical."""
    compute_dtype = compute_dtype or x.dtype
    w = p["weight"]
    scale = p.get("scale")
    cdims = (((x.ndim - 1,), (0,)), ((), ()))
    # int8 weights feed the dot directly (mixed-precision dot_general):
    # XLA:TPU converts the int8 operand in VMEM after the (halved) HBM
    # fetch, where an explicit astype can materialise a converted copy
    # outside the dot fusion.
    out = jax.lax.dot_general(
        x, w, cdims, preferred_element_type=jnp.float32,
    )
    if scale is not None:
        out = out * scale.reshape((1,) * (out.ndim - 1) + (-1,))
    if "lora_a" in p:
        low = jax.lax.dot_general(
            x, p["lora_a"].astype(compute_dtype), cdims,
            preferred_element_type=jnp.float32,
        )
        out = out + p["lora_scale"] * jax.lax.dot_general(
            low.astype(compute_dtype), p["lora_b"].astype(compute_dtype),
            cdims, preferred_element_type=jnp.float32,
        )
    if adapter_ids is not None and "lora_pool_a" in p:
        pa = p["lora_pool_a"].astype(compute_dtype)   # [N, in, r]
        pb = p["lora_pool_b"].astype(compute_dtype)   # [N, r, out]
        psc = p["lora_pool_scale"]                    # [N] f32
        n_slots = pa.shape[0]
        onehot = jax.nn.one_hot(
            adapter_ids, n_slots, dtype=jnp.float32
        )                                             # [..., S, N]
        low = jnp.einsum(
            "...si,nir->...snr", x, pa,
            preferred_element_type=jnp.float32,
        )
        # mask by slot selection: only the token's own adapter row
        # survives, so the n-sum in the second einsum IS the gather
        low = (low * onehot[..., None]).astype(compute_dtype)
        delta = jnp.einsum(
            "...snr,nro->...so", low, pb,
            preferred_element_type=jnp.float32,
        )
        tok_scale = jnp.einsum(
            "...sn,n->...s", onehot, psc.astype(jnp.float32)
        )
        out = out + tok_scale[..., None] * delta
    b = p.get("bias")
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out.astype(compute_dtype)


def embed_lookup(p: dict, tokens, compute_dtype):
    """Embedding lookup through a possibly row-quantized table."""
    w = p["weight"]
    emb = w[tokens]
    if w.dtype == jnp.int8:
        emb = emb.astype(jnp.float32) * p["embed_scale"][tokens]
    return emb.astype(compute_dtype)
