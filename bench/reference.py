"""Plain reference of the served models, in float32, and its control.

A pre-norm decoder written from the layer equations alone: token
embedding; per layer ``x += Wo attn(rope(Wq n1(x)), rope(Wk n1(x)),
Wv n1(x))`` with causal grouped-query softmax attention (query head ``j``
reads key/value head ``j // (n_heads / n_kv_heads)``), rotate-half RoPE
over the whole head (``theta ** (-2i / head_dim)``), then
``x += W_down(silu(W_gate n2(x)) * W_up n2(x))``; a final norm and the LM
head over the true vocabulary.  LayerNorm is ``(x - mean) / sqrt(var +
eps) * scale + bias``; RMSNorm is ``x / sqrt(mean(x^2) + eps) * (1 +
scale)``.  It imports nothing of the program under test: it reads the
configuration file and the weights the benchmark made.

``mode="float32"`` computes every product at ``highest`` precision.
``mode="fp8"`` is the control: every projection quantizes both operands
to float8 e4m3 with a scale per row of the activations and per output
column of the weights, the nearest precision below the configuration's
bfloat16 that a serving path would be tempted by; attention itself stays
in float32.

The forward runs layer by layer, over blocks of a few sequences, so that
it fits beside the weights once the engine's state is freed.
"""

from __future__ import annotations

import functools
import math

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


def _layer(x, layers, i, *, model, mode):
    """Layer ``i`` (traced index into the stacked weights) on x (B, T, d)."""
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), layers)
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    B, T, d = x.shape
    nh, nkv = model["n_heads"], model["n_kv_heads"]
    hd = lp["attn"]["wq"].shape[-1]
    h = _norm(x, lp["ln1"], model)
    q = _mm(h, lp["attn"]["wq"].reshape(d, nh * hd), mode).reshape(B, T, nh, hd)
    k = _mm(h, lp["attn"]["wk"].reshape(d, nkv * hd), mode).reshape(B, T, nkv, hd)
    v = _mm(h, lp["attn"]["wv"].reshape(d, nkv * hd), mode).reshape(B, T, nkv, hd)
    q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    s = jnp.einsum("bqnh,bknh->bnqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = np.tril(np.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bnqk,bknh->bqnh", jax.nn.softmax(s, -1), v, precision=HIGHEST)
    x = x + _mm(o.reshape(B, T, nh * hd), lp["attn"]["wo"].reshape(nh * hd, d), mode)
    h = _norm(x, lp["ln2"], model)
    g = _mm(h, lp["ffn"]["w_gate"], mode)
    u = _mm(h, lp["ffn"]["w_up"], mode)
    return x + _mm(jax.nn.silu(g) * u, lp["ffn"]["w_down"], mode)


def _head(embed: dict, model: dict) -> jax.Array:
    """The LM head over the true vocabulary, (d, V)."""
    V = model["vocab"]
    if model["tie_embeddings"]:
        return embed["tok"][:V].T
    return embed["unembed"][:, :V]


ROWS = 128      # positions per logits call are padded to a multiple of this


@functools.lru_cache(maxsize=None)
def _fns(model_items: tuple, mode: str):
    model = dict(model_items)
    layer = jax.jit(functools.partial(_layer, model=model, mode=mode))

    @jax.jit
    def embed(tok_table, tokens):
        return tok_table[tokens].astype(jnp.float32)

    @jax.jit
    def logits(embed_params, final_norm, x, rows, cols):
        h = _norm(x[rows, cols], final_norm, model)                    # (N, d)
        return _mm(h, _head(embed_params, model), mode)                # (N, V)

    return layer, embed, logits


def logits_at(params: dict, model: dict, tokens: np.ndarray, rows: np.ndarray,
              cols: np.ndarray, mode: str = "float32", block: int = 4) -> jax.Array:
    """Logits (N, vocab) at positions ``(rows[i], cols[i])`` of the token
    matrix ``tokens`` (K, T), each from the causal prefix up to it.
    ``rows`` is non-decreasing."""
    if np.any(np.diff(rows) < 0):
        raise ValueError("rows must be non-decreasing")
    layer, embed, logits = _fns(tuple(sorted(model.items())), mode)
    out = []
    for b0 in range(0, tokens.shape[0], block):
        sel = (rows >= b0) & (rows < b0 + block)
        if not sel.any():
            continue
        blk = np.zeros((block, tokens.shape[1]), np.int32)
        part = tokens[b0:b0 + block]
        blk[: part.shape[0]] = part
        x = embed(params["embed"]["tok"], jnp.asarray(blk))
        for i in range(model["n_layers"]):
            x = layer(x, params["layers"], jnp.int32(i))
        r, c = rows[sel] - b0, cols[sel]
        n = len(r)
        padded = -(-n // ROWS) * ROWS
        r = np.concatenate([r, np.zeros(padded - n, r.dtype)]).astype(np.int32)
        c = np.concatenate([c, np.zeros(padded - n, c.dtype)]).astype(np.int32)
        out.append(logits(params["embed"], params["final_norm"], x,
                          jnp.asarray(r), jnp.asarray(c))[:n])
    return jnp.concatenate(out)
