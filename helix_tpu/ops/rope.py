"""Rotary position embeddings (RoPE), including Llama-3 frequency scaling.

Computed on the fly from position ids rather than precomputed tables so the
same function serves ragged prefill (arbitrary positions per token) and
decode (one position per sequence) without gather ops that would break XLA
fusion.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def rope_frequencies(
    head_dim: int,
    theta: float = 500000.0,
    scaling: dict | None = None,
) -> np.ndarray:
    """Inverse frequencies, with optional Llama-3-style rope scaling.

    ``scaling`` follows HF config ``rope_scaling`` with
    ``rope_type=llama3``: {factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings}.
    """
    inv_freq = 1.0 / (
        theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    )
    if scaling is not None and not isinstance(scaling, dict):
        scaling = dict(scaling)   # configs store it as a sorted item tuple
    if scaling and scaling.get("rope_type", scaling.get("type")) == "llama3":
        factor = scaling["factor"]
        low = scaling["low_freq_factor"]
        high = scaling["high_freq_factor"]
        orig = scaling["original_max_position_embeddings"]
        wavelen = 2 * np.pi / inv_freq
        # three bands: high-freq untouched, low-freq divided by factor,
        # middle smoothly interpolated
        smooth = (orig / wavelen - low) / (high - low)
        smooth = np.clip(smooth, 0.0, 1.0)
        scaled = inv_freq / factor
        inv_freq = (1 - smooth) * scaled + smooth * inv_freq
    if scaling and scaling.get("rope_type", scaling.get("type")) == "yarn":
        inv_freq = _yarn_frequencies(inv_freq, head_dim, theta, scaling)
    return inv_freq.astype(np.float32)


def _yarn_frequencies(inv_freq, dim, theta, scaling):
    """YaRN (Peng et al. 2023, as DeepSeek-V2 uses it): dimensions that
    turn more than ``beta_fast`` times over the original context keep
    their frequency, those that turn less than ``beta_slow`` times are
    interpolated by ``factor``, with a linear ramp between."""
    factor = scaling["factor"]
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * np.log(orig / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(int(np.floor(correction_dim(scaling.get("beta_fast", 32)))), 0)
    high = min(int(np.ceil(correction_dim(scaling.get("beta_slow", 1)))),
               dim - 1)
    ramp = (np.arange(dim // 2, dtype=np.float64) - low) / max(
        high - low, 1e-3)
    keep = 1.0 - np.clip(ramp, 0.0, 1.0)
    return inv_freq / factor * (1.0 - keep) + inv_freq * keep


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: ``0.1 * mscale * ln(factor) + 1``
    (1 for no scaling)."""
    if factor <= 1 or not mscale:
        return 1.0
    return 0.1 * mscale * float(np.log(factor)) + 1.0


def yarn_attention_scales(scaling) -> tuple:
    """``(cos/sin scale, softmax scale factor)`` of a YaRN ``rope_scaling``:
    the rotation is scaled by ``mscale(f, mscale) / mscale(f,
    mscale_all_dim)`` and the attention scores by ``mscale(f,
    mscale_all_dim) ** 2`` (DeepSeek-V2).  A config that states the
    rotation's scale itself (``attention_factor``, Hugging Face's key) gets
    that on cos and sin and nothing on the scores.  ``(1, 1)`` without
    YaRN."""
    scaling = dict(scaling or ())
    if scaling.get("rope_type", scaling.get("type")) != "yarn":
        return 1.0, 1.0
    if scaling.get("attention_factor"):
        return float(scaling["attention_factor"]), 1.0
    f = scaling["factor"]
    all_dim = yarn_mscale(f, scaling.get("mscale_all_dim", 0))
    rot = yarn_mscale(f, scaling.get("mscale", 1)) / all_dim
    return rot, all_dim * all_dim


def apply_rope_interleaved(x, positions, inv_freq, scale: float = 1.0):
    """Rotate the pairs ``(2i, 2i+1)`` of the last axis (DeepSeek's
    pairing) and return them DE-INTERLEAVED: first halves then second
    halves.  A score ``q . k`` does not change under a permutation both
    sides share, and the halves layout needs no lane shuffle back.

    x: ``[..., seq, (heads,) dim]``; positions broadcastable to
    ``[..., seq]``; a head axis, if any, sits between seq and dim."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    if x.ndim == angles.ndim + 1:
        cos, sin = cos[..., None, :], sin[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope(x, positions, inv_freq, scale: float = 1.0):
    """Rotate q or k.

    x:         [..., seq, heads, head_dim]
    positions: broadcastable to [..., seq] (int32)
    inv_freq:  [rotary width // 2]: a table narrower than the head rotates
               the head's FIRST ``2 * len(inv_freq)`` dims (paired across
               that width's halves) and passes the rest through
    scale:     on cos and sin (YaRN's attention factor): it acts on the
               rotated dims only
    """
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., seq, w/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., seq, 1, w/2]
    sin = jnp.sin(angles)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    width = 2 * inv_freq.shape[-1]
    xf = x.astype(jnp.float32)
    rest = []
    if width < x.shape[-1]:
        xf, rest = xf[..., :width], [xf[..., width:]]
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin] + rest, axis=-1)
    return out.astype(x.dtype)
