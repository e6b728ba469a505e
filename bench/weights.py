"""Seeded random weights for a configuration, made on the device in one
jitted call, in the layout the serving engine takes and the type it
serves them in.

The distribution is the benchmark's own: projections N(0, 1/fan_in) over
their whole input width, the embedding table N(0, 0.02^2), the untied LM
head N(0, 1/d_model), and norm parameters perturbed by N(0, 0.1^2) around
their identity (LayerNorm scale 1 + e, bias e; RMSNorm's zero-centred
scale e, which the model applies as 1 + e).  The table rows past the true
vocabulary (the program pads it to a multiple of 256) are drawn too and
never read.
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp

NORM_DTYPE = jnp.float32


def padded_vocab(vocab: int) -> int:
    """The vocabulary rounded up to a multiple of 256, as the engine holds it."""
    return (vocab + 255) // 256 * 256


def head_dim(model: dict) -> int:
    return model["head_dim"] or model["d_model"] // model["n_heads"]


def layout(model: dict) -> dict:
    """``{path: (shape, dtype, std, mean)}`` of every parameter."""
    d, f, L = model["d_model"], model["d_ff"], model["n_layers"]
    nh, nkv, h = model["n_heads"], model["n_kv_heads"], head_dim(model)
    vp = padded_vocab(model["vocab"])
    dt = jnp.dtype(model["dtype"])
    out = {
        ("embed", "tok"): ((vp, d), dt, 0.02, 0.0),
        ("layers", "attn", "wq"): ((L, d, nh, h), dt, 1 / math.sqrt(d), 0.0),
        ("layers", "attn", "wk"): ((L, d, nkv, h), dt, 1 / math.sqrt(d), 0.0),
        ("layers", "attn", "wv"): ((L, d, nkv, h), dt, 1 / math.sqrt(d), 0.0),
        ("layers", "attn", "wo"): ((L, nh, h, d), dt, 1 / math.sqrt(nh * h), 0.0),
        ("layers", "ffn", "w_gate"): ((L, d, f), dt, 1 / math.sqrt(d), 0.0),
        ("layers", "ffn", "w_up"): ((L, d, f), dt, 1 / math.sqrt(d), 0.0),
        ("layers", "ffn", "w_down"): ((L, f, d), dt, 1 / math.sqrt(f), 0.0),
    }
    if not model["tie_embeddings"]:
        out[("embed", "unembed")] = ((d, vp), dt, 1 / math.sqrt(d), 0.0)
    norms = [(("layers", "ln1"), (L, d)), (("layers", "ln2"), (L, d)),
             (("final_norm",), (d,))]
    for path, shape in norms:
        if model["norm"] == "layernorm":
            out[path + ("scale",)] = (shape, NORM_DTYPE, 0.1, 1.0)
            out[path + ("bias",)] = (shape, NORM_DTYPE, 0.1, 0.0)
        else:
            out[path + ("scale",)] = (shape, NORM_DTYPE, 0.1, 0.0)
    return out


def _key(seed: int) -> jax.Array:
    """A key from any whole number (more than 32 bits too)."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _build(key, model_items):
    tree: dict = {}
    for i, (path, (shape, dtype, std, mean)) in enumerate(model_items):
        leaf = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
        leaf = (leaf * jnp.asarray(std, dtype) + jnp.asarray(mean, dtype)).astype(dtype)
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    return tree


def make(model: dict, seed: int, device=None) -> dict:
    """The weights of ``model`` for ``seed``, on ``device`` (JAX's default
    if None), from one jitted call."""
    items = tuple(layout(model).items())
    kw = {}
    if device is not None:
        kw["out_shardings"] = jax.sharding.SingleDeviceSharding(device)
    fn = jax.jit(functools.partial(_build, model_items=items), **kw)
    with jax.default_device(device) if device is not None else contextlib.nullcontext():
        return jax.block_until_ready(fn(_key(seed)))
