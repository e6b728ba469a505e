"""Seeded random weights for a parameter layout, made on the device in one
jitted call, in the type the serving engine serves them in.  The layout
(each parameter's path, shape, type and distribution) is the
architecture module's (``bench/arch/<arch>.py``, ``layout``); the
builder is shared.

Every layout draws from the benchmark's own distribution: projections
N(0, 1/fan_in) over their whole input width, the embedding table
N(0, 0.02^2), the untied LM head N(0, 1/d_model), and norm parameters
perturbed by N(0, 0.1^2) around their identity (LayerNorm scale 1 + e,
bias e; RMSNorm's zero-centred scale e, which the model applies as
1 + e).  The table rows past the true vocabulary (the program pads it to
a multiple of 256) are drawn too and never read.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

NORM_DTYPE = jnp.float32


def padded_vocab(vocab: int) -> int:
    """The vocabulary rounded up to a multiple of 256, as the engine holds it."""
    return (vocab + 255) // 256 * 256


def _key(seed: int) -> jax.Array:
    """A key from any whole number (more than 32 bits too)."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _build(key, layout_items):
    tree: dict = {}
    for i, (path, (shape, dtype, std, mean)) in enumerate(layout_items):
        leaf = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
        leaf = (leaf * jnp.asarray(std, dtype) + jnp.asarray(mean, dtype)).astype(dtype)
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    return tree


def make(layout: dict, seed: int, device=None) -> dict:
    """The weights of ``layout`` (``{path: (shape, dtype, std, mean)}``)
    for ``seed``, on ``device`` (JAX's default if None), from one jitted
    call; leaf ``i`` of the layout's order draws from ``fold_in(key, i)``."""
    items = tuple(layout.items())
    kw = {}
    if device is not None:
        kw["out_shardings"] = jax.sharding.SingleDeviceSharding(device)
    fn = jax.jit(functools.partial(_build, layout_items=items), **kw)
    with jax.default_device(device) if device is not None else contextlib.nullcontext():
        return jax.block_until_ready(fn(_key(seed)))
