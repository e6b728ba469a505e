"""Numeric helpers shared by the plain references of every architecture
(``bench/arch/<arch>.py``, ``logits_at``), in float32, and their float8
control.

``mode="float32"`` computes every product at ``highest`` precision.
``mode="fp8"`` is the control: every projection quantizes both operands
to float8 e4m3 with a scale per row of the activations and per output
column of the weights, the nearest precision below the configurations'
bfloat16 that a serving path would be tempted by; attention itself stays
in float32.  LayerNorm is ``(x - mean) / sqrt(var + eps) * scale +
bias``; RMSNorm is ``x / sqrt(mean(x^2) + eps) * (1 + scale)``; RoPE
rotates half of the head against the other half.  Nothing here imports
the program under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _quant(a: jax.Array, axis: int) -> jax.Array:
    """``a`` rounded to float8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(x: jax.Array, w: jax.Array, mode: str) -> jax.Array:
    """``x[..., k] @ w[k, n]`` in float32, or through float8 in ``fp8`` mode."""
    w = w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _quant(x, -1), _quant(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _norm(x: jax.Array, p: dict, model: dict) -> jax.Array:
    eps = model["norm_eps"]
    if model["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * (1.0 + p["scale"])


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (B, T, heads, hd), positions 0..T-1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(x.shape[1])[:, None] * inv[None, :]          # (T, hd/2)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


ROWS = 128      # positions per logits call are padded to a multiple of this
