"""Mixture-of-experts FFN: one router in three forms, two dispatches.

- **Router** (``route``): per-token logits over the experts in fp32.
  Mixtral: top-k of the logits, softmax over the k (``moe_renormalize``).
  DeepSeek-V2: softmax over ALL experts, the top-k probabilities kept as
  they are, times ``routed_scaling_factor``.  LFM2 (``moe_scoring``
  "sigmoid"): a sigmoid score an expert, the top-k chosen on score + a
  learned bias, weighted by the scores WITHOUT the bias, renormalised
  over the k with the published ``1e-6`` in the divisor.
- **Dropless grouped dispatch** (``expert_capacity_factor == 0``, what
  DeepSeek configs get): the ``T * k`` (token, choice) assignments are
  sorted by expert and each projection is ONE grouped matrix product over
  the experts' stacked weights, int8 weights read as stored.  On a TPU it
  is this repo's Pallas kernel (``ops/grouped_matmul.py``: a grid step is
  one (row tile, expert) visit against the expert's whole ``[K, N]``
  weight, the layer picked from the whole stack by the block index, the
  per-channel scale in the kernel's last step, gate and up in one call
  that writes ``act(gate) * up``); on a CPU, under ``backend="reference"``
  or for widths the kernel refuses it is ``jax.lax.ragged_dot``, the
  oracle the tests hold the kernel to (``grouped_backend``).
  Compute follows the tokens routed, at every shape: prefill, chunk,
  decode, mixed.  Nothing is ever dropped.  Padding rows and idle slots
  (``token_mask`` False) are sorted past the last group and multiply
  nothing.
- **Capacity dispatch** (``expert_capacity_factor > 0``, Mixtral): the
  GShard/Switch algebra, dense einsums over an ``[experts, capacity]``
  buffer, which an ``ep`` mesh axis shards with all-to-alls.  Each expert
  processes at most ``C = factor * T * k / X`` tokens a call; overflow is
  dropped from that expert and counted; decode (S == 1) runs with C = T.

- **Held experts** (``ModelConfig.held_experts = (lo, hi)``): the chip is
  one expert-parallel rank.  The router scores all ``num_experts`` at the
  published top-k; the dropless dispatch sorts, visits and combines the
  assignments to experts ``[lo, hi)`` alone, and ``moe_ffn`` returns the
  part of the layer's sum they give.  No code stands in for the other
  ranks or their exchange.

``moe_ffn`` returns the routed experts' sum only; a shared expert is a
dense MLP the layer adds beside it (``models/llama.py``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from helix_tpu.ops.attention import resolve_backend
from helix_tpu.ops.grouped_matmul import (
    check_grouped_geometry,
    glu,
    grouped_matmul_tpu,
    row_tile,
    visit_plan,
)
from helix_tpu.ops.paged_kernel import UnsupportedKernelGeometry


def _expert_dense(h_in, wp, spec):
    """Batched per-expert matmul over stacked weights, feeding int8
    weight-only storage DIRECTLY into the einsum (mixed-precision dot:
    XLA converts the int8 operand in VMEM after the halved HBM fetch —
    ops/quant.py maybe_dequant_dense's convention) and rescaling the
    output per channel."""
    w = wp["weight"]
    out = jnp.einsum(spec, h_in, w, preferred_element_type=jnp.float32)
    scale = wp.get("scale")
    if scale is not None:
        # scale: [X, 1, out] — broadcasts over the capacity dim
        out = out * scale.astype(jnp.float32)
    return out


def route(xf, router_p, cfg, expert_bias=None):
    """Router: ``xf [T, E]`` -> ``(weights [T, k] f32, experts [T, k])``.
    ``expert_bias [X]``: the sigmoid router's learned selection bias."""
    k = cfg.num_experts_per_tok
    logits = jnp.dot(
        xf.astype(jnp.float32), router_p.astype(jnp.float32)
    )                                               # [T, X]
    if cfg.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        chosen = scores if expert_bias is None else (
            scores + expert_bias.astype(jnp.float32))
        _, top_idx = jax.lax.top_k(chosen, k)
        top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
        if cfg.moe_renormalize:
            top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-6)
        return top_w * cfg.routed_scaling_factor, top_idx
    if cfg.moe_renormalize:
        top_vals, top_idx = jax.lax.top_k(logits, k)
        return jax.nn.softmax(top_vals, axis=-1), top_idx
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, k)
    return top_w * cfg.routed_scaling_factor, top_idx


STATS = 6

# A verification hook: a callable that, WHILE SET WHEN A PROGRAM IS TRACED,
# receives every expert layer's choices through ``jax.debug.callback``:
# ``PROBE(layer, tokens [B, S], positions [B, S], valid [T], experts [T,
# k])``, ``layer`` the layer's index in the model and ``tokens`` the pass's
# input ids (``helix_tpu/testing/moe_probe.py`` keeps them by layer, position
# and token; ``tests/test_deltanet_gqa_moe.py`` and ``chip_smoke_deepseek.py``
# run the plain reference on them).  None: nothing is traced
PROBE = None


def expert_load_stats(top_idx, valid, X, dropped=0, tile_fill=0.0,
                      held=None):
    """``[dropped, routed, busiest expert's tokens over the mean, distinct
    experts touched, tile fill, away]`` of one layer's routing, f32.
    ``tile_fill`` is the dropless path's (``_grouped_experts``); the
    capacity path walks no row tiles and reports 0.  With ``held = (lo,
    hi)`` the load is that of the experts held here (``routed`` counts the
    assignments to them) and ``away`` the assignments to experts elsewhere,
    which this chip does not compute."""
    load = jnp.sum(
        jax.nn.one_hot(top_idx, X, dtype=jnp.float32)
        * valid[:, None, None].astype(jnp.float32), axis=(0, 1)
    )                                               # [X]
    away = jnp.float32(0)
    if held is not None:
        away = jnp.sum(load) - jnp.sum(load[held[0]:held[1]])
        load = load[held[0]:held[1]]
    routed = jnp.sum(load)
    ratio = jnp.max(load) * load.shape[0] / jnp.maximum(routed, 1.0)
    return jnp.stack([
        jnp.asarray(dropped, jnp.float32), routed, ratio,
        jnp.sum((load > 0).astype(jnp.float32)),
        jnp.asarray(tile_fill, jnp.float32), away,
    ])


def grouped_backend(widths, backend: Optional[str] = None) -> str:
    """Which grouped product the dropless path runs for expert weights of
    ``widths`` (``(K, N)`` pairs): ``"pallas"`` (``ops/grouped_matmul.py``)
    where the dispatcher the attention kernels use says so and the kernel
    takes every width, else ``"xla"`` (``lax.ragged_dot``)."""
    if resolve_backend(backend) != "pallas":
        return "xla"
    try:
        for K, N in widths:
            check_grouped_geometry(K, N)
    except UnsupportedKernelGeometry:
        return "xla"
    return "pallas"


def _grouped_experts(xf, top_w, top_idx, valid, experts_p, X, act,
                     layer=None, backend=None, interpret=False, held=None,
                     limit: float = 0.0):
    """Dropless: sort the assignments by expert, the grouped products,
    unsort, weighted sum over each token's k choices.  Also returns the
    products' tile fill: rows routed over rows walked, ``routed / (visits
    * tm)``, of the kernel's visit plan (whichever product ran).

    ``held = (lo, hi)``: ``experts_p`` holds experts ``[lo, hi)`` of the
    ``X`` the router scored (one expert-parallel rank's).  An assignment to
    an expert elsewhere is an invalid token's: out of the sort, the visit
    plan and the combine.  What comes back is the part of the layer's sum
    these experts give.

    ``layer`` (a traced index): ``experts_p`` is then a whole STACK of
    layers' experts (``[n, X, ...]`` leaves) of which this layer's are
    read in place: a per-layer slice would be copied out for a kernel."""
    T, E = xf.shape
    k = top_idx.shape[1]
    with jax.named_scope("moe.dispatch"):
        # invalid tokens go to a sentinel past the last expert: they sort
        # to the end, belong to no group and are multiplied by nothing
        here = valid[:, None]
        if held is not None:
            lo, hi = held
            here = here & (top_idx >= lo) & (top_idx < hi)
            # the rows an expert gets on average stay what they were; the
            # groups are the held experts alone
            rows_mean = T * k * (hi - lo) // X
            top_idx, X = top_idx - lo, hi - lo
        else:
            rows_mean = T * k
        flat_e = jnp.where(here, top_idx, X).reshape(-1)            # [T*k]
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        group_sizes = jnp.sum(
            jax.nn.one_hot(flat_e, X, dtype=jnp.int32), axis=0
        )                                                           # [X]
        xs = xf[order // k]                                         # [T*k, E]

    # an ungated expert has no ``w_gate``
    names = tuple(n for n in ("w_gate", "w_up", "w_down") if n in experts_p)
    if layer is None:
        # one layer's experts are a stack of one
        experts_p = {n: jax.tree.map(lambda a: a[None], experts_p[n])
                     for n in names}
        layer = 0
    kernel = grouped_backend(
        [experts_p[n]["weight"].shape[-2:] for n in names], backend
    ) == "pallas"
    tm = row_tile(rows_mean, X)
    plan = visit_plan(group_sizes, T * k, tm)
    visits = plan[-1][0]
    fill = jnp.sum(group_sizes) / jnp.maximum(visits * tm, 1)

    with jax.named_scope("moe.experts"):
        if kernel:
            y = experts_pallas(
                xs, plan, tm, experts_p, layer, act, interpret, limit)
        else:
            y = experts_xla(
                xs, group_sizes, jnp.minimum(sorted_e, X - 1), experts_p,
                layer, act, limit)
    with jax.named_scope("moe.combine"):
        # rows past the last group hold whatever the product left there
        y = jnp.where((sorted_e < X)[:, None], y, 0.0)
        y = y[jnp.argsort(order)].reshape(T, k, E)
        w = top_w * here.astype(top_w.dtype)
        return jnp.einsum("tk,tke->te", w, y), fill


def experts_pallas(xs, plan, tm, experts_p, layer, act, interpret,
                   limit: float = 0.0):
    """The three products through ``ops/grouped_matmul.py``: gate and up
    in one call, down in a second, one visit plan for both.  An ungated
    expert (no ``w_gate``): ``act(x W_up)`` in the first call."""
    kw = dict(tm=tm, interpret=interpret)
    up, down = experts_p["w_up"], experts_p["w_down"]
    if "w_gate" in experts_p:
        gate = experts_p["w_gate"]
        h = grouped_matmul_tpu(
            xs, gate["weight"], plan, layer, scale=gate.get("scale"),
            w2=up["weight"], scale2=up.get("scale"), act=act, limit=limit,
            out_dtype=xs.dtype, **kw)
    else:
        h = grouped_matmul_tpu(
            xs, up["weight"], plan, layer, scale=up.get("scale"), act=act,
            out_dtype=xs.dtype, **kw)
    return grouped_matmul_tpu(
        h, down["weight"], plan, layer, scale=down.get("scale"), **kw)


def experts_xla(xs, group_sizes, e_row, experts_p, layer, act,
                limit: float = 0.0):
    """The same three products as ``lax.ragged_dot`` over this layer's
    slice of the stack: the oracle, and what a CPU runs."""
    def grouped(h, wp):
        w = wp["weight"][layer]
        out = jax.lax.ragged_dot(
            h, w, group_sizes,
            preferred_element_type=jnp.float32,
            # int8 weights meet the activations as stored; there is no
            # higher-precision product of that pair for a global matmul
            # precision to ask for
            precision=(jax.lax.Precision.DEFAULT
                       if w.dtype == jnp.int8 else None),
        )
        if "scale" in wp:
            # [X, 1, out] per-output-channel scales, one row per sorted
            # assignment
            out = out * wp["scale"][layer][e_row, 0].astype(jnp.float32)
        return out

    up = grouped(xs, experts_p["w_up"])
    mid = act(up) if "w_gate" not in experts_p else glu(
        grouped(xs, experts_p["w_gate"]), up, act, limit)
    return grouped(mid.astype(xs.dtype), experts_p["w_down"])


def moe_ffn(x, router_p, experts_p, cfg, act, token_mask=None,
            return_dropped=False, return_stats=False, stacked_experts=None,
            backend=None, interpret=False, expert_bias=None,
            decode_rows: int = 0, router_x=None, probe=None):
    """x: [B, S, E] -> the routed experts' weighted sum [B, S, E].  With
    ``return_dropped`` also the int32 count of (token, choice) assignments
    this call dropped to capacity overflow (always 0 on the dropless
    path); with ``return_stats`` instead ``expert_load_stats``'s vector.

    router_p: [E, X] (dequantised); experts_p: {"w_gate"/"w_up":
    {"weight": [X, E, F][, "scale"]}, "w_down": {...}}: int8 weight-only
    trees pass through unchanged; without ``w_gate`` the experts are ungated,
    ``W_down act(W_up x)`` (dropless path only).

    token_mask [B, S] (optional): False tokens (padding, inactive decode
    slots) are EXCLUDED from routing entirely, so a request's outputs
    never depend on garbage riding the same batch.

    ``stacked_experts = (experts of a whole layer stack, this layer's
    index in it)`` instead of ``experts_p`` (dropless path only).
    ``backend``: as the attention dispatchers take it (``None``: what the
    process' devices call for), for the dropless path's grouped product
    (``grouped_backend``); ``interpret`` runs its kernel in interpret mode
    (the CPU tests).  ``decode_rows``: the last that many tokens are decode
    rows on a prefill's axis (``_capacity_experts``).  ``router_x [B, S,
    E']``: what the router scores where that is not the experts' input (experts
    in a latent: ``x`` is the projected input, ``router_x`` the un-projected).
    ``probe = (layer, tokens, positions)``: what ``PROBE`` is shown beside the
    choices, where one is set."""
    B, S, E = x.shape
    X = cfg.num_experts
    T = B * S
    xf = x.reshape(T, E)
    valid = (
        jnp.ones((T,), jnp.bool_)
        if token_mask is None
        else token_mask.reshape(T)
    )
    with jax.named_scope("moe.router"):
        top_w, top_idx = route(
            xf if router_x is None else router_x.reshape(T, -1), router_p,
            cfg, expert_bias)
    if PROBE is not None and probe is not None:
        jax.debug.callback(
            lambda *a: PROBE(*(jax.device_get(x) for x in a)),
            *probe, valid, top_idx)
    fill = 0.0
    held = cfg.held_experts
    if cfg.expert_capacity_factor > 0:
        if (held is not None or cfg.swiglu_limit or not cfg.mlp_gated
                or router_x is not None):
            raise ValueError(
                "held experts, a clamped SwiGLU, ungated experts and experts "
                "in a latent are the dropless dispatch's: "
                "expert_capacity_factor must be 0")
        out, dropped = _capacity_experts(
            xf, top_w, top_idx, valid, experts_p, cfg, act, S, decode_rows
        )
    else:
        if stacked_experts is not None:
            experts_p, layer = stacked_experts
        else:
            layer = None
        out, fill = _grouped_experts(
            xf, top_w, top_idx, valid, experts_p, X, act, layer, backend,
            interpret, held, cfg.swiglu_limit)
        dropped = jnp.int32(0)
    out = out.reshape(B, S, E).astype(x.dtype)
    if return_stats:
        return out, expert_load_stats(top_idx, valid, X, dropped, fill,
                                      held)
    if return_dropped:
        return out, dropped
    return out


def _capacity_experts(xf, top_w, top_idx, valid, experts_p, cfg, act, S,
                      decode_rows: int = 0):
    """GShard capacity dispatch.  C = factor * T * k / X for prefill
    shapes; decode (S == 1) runs DROPLESS (C = T).  With ``decode_rows``
    the axis is a prefill's tokens and then that many decode rows: the
    prefill tokens meet the capacity they would meet alone, and the decode
    rows get slots of their own behind it, one a row and expert: they
    neither drop nor push a prefill token out."""
    T, E = xf.shape
    X = cfg.num_experts
    k = cfg.num_experts_per_tok
    n_pre = T - decode_rows
    # --- capacity + position of each (token, choice) in its expert ---
    if S == 1:
        C_pre = T                                    # dropless decode
    else:
        C_pre = max(int(cfg.expert_capacity_factor * n_pre * k / X), 1)
    C = C_pre + decode_rows
    # choice-major flattening ranks first choices ahead of second
    # choices across the batch, so capacity overflow drops the weaker
    # assignments first; invalid tokens are routed to a sentinel so they
    # never occupy a capacity slot
    flat_idx = jnp.where(
        jnp.tile(valid, k), top_idx.T.reshape(-1), X
    )                                               # [k*T] expert ids
    onehot = jax.nn.one_hot(flat_idx, X, dtype=jnp.int32)   # [kT, X]
    limit = C_pre
    if decode_rows:
        # positions count within the prefill tokens and within the decode
        # rows apart, the decode rows' from C_pre on
        tail = jnp.tile(jnp.arange(T) >= n_pre, k)  # [kT]
        tail_hot = onehot * tail[:, None]
        pre_hot = onehot - tail_hot
        pos_in_expert = (
            jnp.cumsum(pre_hot, axis=0) - pre_hot
        ) * pre_hot + (
            C_pre + jnp.cumsum(tail_hot, axis=0) - tail_hot
        ) * tail_hot
        limit = jnp.where(tail, C, C_pre)
    else:
        pos_in_expert = (
            jnp.cumsum(onehot, axis=0) - onehot
        )                                           # [kT, X]
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)  # [kT]
    keep = (pos < limit) & (flat_idx < X)
    # capacity-overflow accounting: a valid assignment (real token, real
    # expert) whose position overflowed C — exactly the work that falls
    # back to the residual stream
    dropped = jnp.sum(((~keep) & (flat_idx < X)).astype(jnp.int32))
    # back to [T, k]
    pos = pos.reshape(k, T).T
    keep = keep.reshape(k, T).T

    # --- dispatch/combine tensors ---
    # dispatch[t, x, c] = 1 where token t's choice lands at slot c of
    # expert x; combine carries the softmax weight on the same support
    dispatch = jnp.zeros((T, X, C), jnp.float32)
    combine = jnp.zeros((T, X, C), jnp.float32)
    for j in range(k):      # an unrolled static loop (Mixtral: 2)
        sel = (
            jax.nn.one_hot(top_idx[:, j], X, dtype=jnp.float32)[:, :, None]
            * jax.nn.one_hot(pos[:, j], C, dtype=jnp.float32)[:, None, :]
            * keep[:, j, None, None].astype(jnp.float32)
        )
        dispatch = dispatch + sel
        combine = combine + sel * top_w[:, j, None, None]

    # --- expert buffers + batched SwiGLU over stacked weights ---
    expert_in = jnp.einsum(
        "txc,te->xce", dispatch.astype(xf.dtype), xf
    )                                                       # [X, C, E]
    gate = _expert_dense(expert_in, experts_p["w_gate"], "xce,xef->xcf")
    up = _expert_dense(expert_in, experts_p["w_up"], "xce,xef->xcf")
    h = _expert_dense(
        (act(gate) * up).astype(xf.dtype), experts_p["w_down"],
        "xcf,xfe->xce",
    )                                                       # [X, C, E]
    return jnp.einsum("txc,xce->te", combine, h), dropped
