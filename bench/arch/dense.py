"""Dense decoder: the parameter layout, the plain reference forward and the
work counts of a pre-norm GQA transformer (``"arch": "dense"``).

An architecture module gives the harness three names, and nothing more:

- ``layout(model)``: ``{path: (shape, dtype, std, mean)}`` of every
  parameter, in the engine's layout; :func:`bench.weights.make` draws them.
- ``logits_at(params, model, tokens, rows, cols, mode, block)``: the plain
  reference's logits, ``mode`` ``"float32"`` or the ``"fp8"`` control;
  :func:`bench.check.widest_gap` decides ``correct`` with it.
- ``Shapes.from_model(model)``, with ``decode(positions)`` and
  ``prefill(prompt_len)`` giving (FLOPs, bytes); the per-layer readers
  call them through ``ctx.shapes``.

A configuration names its module by its top-level ``"arch"`` key, and
:func:`bench.spec.arch` loads ``bench/arch/<arch>.py`` from the cell's
root, so another architecture joins as a file of its own.  Such a module
may import the shared pieces: the seeded builder in :mod:`bench.weights`,
the numeric helpers in :mod:`bench.reference`, and
:mod:`bench.work`'s roofline arithmetic.  A count that depends on what a
step did at run time (which experts a routed step hit) is not a shape: it
belongs in a per-layer reader of its own, which reads the program's
counters from ``ctx.spans``.

The reference, written from the layer equations alone: token embedding;
per layer ``x += Wo attn(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x))`` with
causal grouped-query softmax attention (query head ``j`` reads key/value
head ``j // (n_heads / n_kv_heads)``), rotate-half RoPE over the whole
head (``theta ** (-2i / head_dim)``), then
``x += W_down(silu(W_gate n2(x)) * W_up n2(x))``; a final norm and the LM
head over the true vocabulary.  It imports nothing of the program under
test: it reads the configuration file and the weights the benchmark made.
The forward runs layer by layer, over blocks of a few sequences, so that
it fits beside the weights once the engine's state is freed.

The counts are the algorithm's, never the compiled program's: a decode
step reads every weight once, the K/V of the positions each live slot
holds, and writes the new token's K/V; prefill counts the true prompt
length, not the padded bucket, and only the last position's logits.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, ROWS, _mm, _norm, _rope
from bench.weights import NORM_DTYPE, padded_vocab
from bench.work import DTYPE_BYTES, NORM_BYTES


def head_dim(model: dict) -> int:
    return model["head_dim"] or model["d_model"] // model["n_heads"]


# --- parameters --------------------------------------------------------------


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


# --- the plain reference -----------------------------------------------------


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


# --- work counts -------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Shapes:
    """The sizes of a dense decoder that the counts depend on."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tie_embeddings: bool
    norm: str
    dtype_bytes: int

    @classmethod
    def from_model(cls, model: dict) -> "Shapes":
        """From the ``model`` section of a configuration file."""
        return cls(
            n_layers=model["n_layers"], d_model=model["d_model"],
            n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
            head_dim=head_dim(model), d_ff=model["d_ff"], vocab=model["vocab"],
            tie_embeddings=model["tie_embeddings"], norm=model["norm"],
            dtype_bytes=DTYPE_BYTES[model["dtype"]],
        )

    @property
    def layer_matrix_params(self) -> int:
        """Parameters of every layer's projections (all layers)."""
        d, h = self.d_model, self.head_dim
        attn = d * self.n_heads * h * 2 + d * self.n_kv_heads * h * 2
        return self.n_layers * (attn + 3 * d * self.d_ff)

    @property
    def norm_params(self) -> int:
        """Norm parameters: two per layer and the final one."""
        per = 2 * self.d_model if self.norm == "layernorm" else self.d_model
        return (2 * self.n_layers + 1) * per

    @property
    def head_params(self) -> int:
        """The LM head, over the true vocabulary."""
        return self.d_model * self.vocab

    @property
    def kv_bytes_per_position(self) -> int:
        """K and V of one position over all layers."""
        return 2 * self.n_layers * self.n_kv_heads * self.head_dim * self.dtype_bytes

    def param_bytes(self) -> int:
        """Bytes of every parameter as stored: matrices in the served type,
        norms in float32, the embedding table and, untied, the LM head."""
        mats = self.layer_matrix_params + self.head_params
        if not self.tie_embeddings:
            mats += self.head_params
        return mats * self.dtype_bytes + self.norm_params * NORM_BYTES

    def _weights_read(self, tokens: int) -> int:
        """Weights one forward pass reads: projections, norms, the LM head
        and the embedding rows of ``tokens`` input tokens."""
        mats = self.layer_matrix_params + self.head_params + tokens * self.d_model
        return mats * self.dtype_bytes + self.norm_params * NORM_BYTES

    def _attn_flops(self, queries_keys: int) -> int:
        """Scores and weighted values for ``queries_keys`` (query, key) pairs."""
        return 4 * self.n_layers * self.n_heads * self.head_dim * queries_keys

    def decode(self, positions: Iterable[int]) -> tuple[int, int]:
        """(FLOPs, bytes) of one decode step whose live slots hold
        ``positions`` cached positions each before the step."""
        positions = list(positions)
        n = len(positions)
        if not n:
            return 0, 0
        per_token = 2 * (self.layer_matrix_params + self.head_params)
        flops = n * per_token + self._attn_flops(sum(p + 1 for p in positions))
        kv = self.kv_bytes_per_position
        nbytes = self._weights_read(n) + sum(positions) * kv + n * kv
        return flops, nbytes

    def prefill(self, prompt_len: int) -> tuple[int, int]:
        """(FLOPs, bytes) of one prefill of ``prompt_len`` true tokens,
        ending in the last position's logits."""
        p = prompt_len
        flops = (2 * self.layer_matrix_params * p + 2 * self.head_params
                 + self._attn_flops(p * (p + 1) // 2))
        nbytes = self._weights_read(p) + p * self.kv_bytes_per_position
        return flops, nbytes
