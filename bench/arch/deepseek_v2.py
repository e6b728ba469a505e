"""DeepSeek-V2 (arXiv:2405.04434): the parameter layout, the plain reference
forward and the work counts of a chip's share of it (``"arch":
"deepseek_v2"``).  The names and their contract are those of
``bench/arch/dense.py``.

The model: token embedding; ``first_dense_layers`` layers of multi-head
latent attention (MLA) and a dense SwiGLU FFN, then MoE layers of MLA and
routed plus shared experts; a final RMSNorm and an untied LM head.  Every
block is pre-norm, ``x += attn(n1(x)); x += ffn(n2(x))``, RMSNorm
``x / sqrt(mean(x^2) + eps) * (1 + scale)``.

MLA, in the expanded form the reference computes, per token:
``cq = rms(x Wdq) * gq``, ``q = cq Wuq`` split per head into ``q_nope``
(128) and ``q_pe`` (64); ``[c, k_pe] = x Wdkv``, ``c = rms(c) * gkv``
(the 512-wide latent the program caches beside ``k_pe``);
``k_nope = c Wuk``, ``v = c Wuv``; ``q_pe`` and the one shared ``k_pe``
rotated by YaRN RoPE; causal softmax of ``(q_nope . k_nope + q_pe . k_pe)
* scale`` with ``scale = mscale(factor, mscale_all_dim)^2 / sqrt(192)``;
``y = concat_heads(p v) Wo``.  YaRN: inverse frequencies
``theta^(-2i/64)`` kept above the dims whose wavelength turns
``beta_fast`` times in the original 4096 positions, divided by
``factor`` below those turning ``beta_slow`` times, a linear ramp between;
cos and sin times ``mscale(factor, mscale) / mscale(factor,
mscale_all_dim)`` (1 here), ``mscale(s, m) = 0.1 m ln s + 1``.

MoE: ``p = softmax(h Wr)`` over all routed experts (160); a group's score
is its best expert's probability (8 groups of 20); the best
``topk_group`` (3) groups are kept, the top ``top_k`` (6) experts among
them chosen, their gates ``p`` times ``routed_scaling_factor`` (16) and
not renormalised.  The chip's share adds only its held experts' part,
``sum_{e held} gate_e * W_down_e(silu(W_gate_e h) * W_up_e h)``, and every
chip adds the shared experts (one SwiGLU of width 2 x 1536).

Departures, each equivalent on random weights: RoPE rotates half of the
64 dims against the other half where the published code interleaves them
(q and k are permuted alike, so the scores are equal); top-k ties go to
the lower index.  It imports nothing of the program under test.

The forward runs one sequence at a time over all its positions (one
shape, so it compiles once), layer by layer, attention over blocks of
heads, so that a 4096-position request fits beside the weights once the
engine's state is freed.

The counts are the algorithm's: a decode step reads every weight once
(every held expert too: in the deployment each expert takes about
n x 6 / 160 x 20 = 48 of a full batch's tokens a step), the latent cache
(576 values a layer) of the positions each live slot holds, and writes
one row a slot; attention is counted in the absorbed form over the live
positions, routed experts at ``n x top_k x held / n_experts`` tokens.
Prefill counts the true prompt length in the expanded form.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, ROWS, _mm, _norm
from bench.weights import NORM_DTYPE, padded_vocab
from bench.work import DTYPE_BYTES, NORM_BYTES

# Router weights N(0, (ROUTER_LOGIT_STD / sqrt(d_model))^2): router logits
# of std 0.25 on a normed input, so the top expert's probability is about
# twice the mean and a top-6 gate (16 x a probability, not renormalised)
# about 0.17.  bfloat16 rounding swaps an expert at the top-6 boundary now
# and then, as often at any router scale (the rounding and the margins
# scale alike), and a swap moves the layer's output by that expert's gated
# output, which grows with the scale (at 512 wide, 9 layers, 4100 served
# tokens a seed on the CPU the served tokens' widest gap read 1.16-1.95 at
# std 1 and 0.25-0.44 at 0.25, the float8 control's 2.41-3.36 and
# 1.36-1.95).  Each routed expert's down projection is drawn at
# 1 / sqrt(width x top_k), the fan-in of the top_k outputs a token sums, so
# that a swap moves the output by less again (up to 4000 positions, 512
# wide, on the CPU: 0.56-0.92 with the plain 1 / sqrt(width), 0.13-0.19
# with this, 0.09-0.11 where nothing swaps; the control 1.55-2.13).
ROUTER_LOGIT_STD = 0.25
HEAD_BLOCK = 16          # attention heads per block


def _held(moe: dict) -> tuple:
    held = moe.get("experts_held") or (0, moe["num_experts"])
    return int(held[0]), int(held[1])


# --- parameters --------------------------------------------------------------


def layout(model: dict) -> dict:
    """``{path: (shape, dtype, std, mean)}`` of every parameter."""
    d, nh, L = model["d_model"], model["n_heads"], model["n_layers"]
    m, e = model["mla"], model["moe"]
    nd = model["first_dense_layers"]
    vp = padded_vocab(model["vocab"])
    dt = jnp.dtype(model["dtype"])
    r, qr, rope = m["kv_lora_rank"], m["q_lora_rank"], m["qk_rope_head_dim"]
    qk, vd = m["qk_nope_head_dim"] + rope, m["v_head_dim"]
    f, fs, held = e["d_ff_expert"], e["d_ff_shared"] * e["num_shared_experts"], _held(e)[1]
    sd = lambda fan_in: 1 / math.sqrt(fan_in)

    def attn(group, n):
        return {
            (group, "attn", "w_dq"): ((n, d, qr), dt, sd(d), 0.0),
            (group, "attn", "q_norm_scale"): ((n, qr), NORM_DTYPE, 0.1, 1.0),
            (group, "attn", "w_uq"): ((n, qr, nh, qk), dt, sd(qr), 0.0),
            (group, "attn", "w_dkv"): ((n, d, r + rope), dt, sd(d), 0.0),
            (group, "attn", "w_uk"): ((n, r, nh, m["qk_nope_head_dim"]), dt, sd(r), 0.0),
            (group, "attn", "w_uv"): ((n, r, nh, vd), dt, sd(r), 0.0),
            (group, "attn", "w_o"): ((n, nh, vd, d), dt, sd(nh * vd), 0.0),
            (group, "attn", "kv_norm_scale"): ((n, r), NORM_DTYPE, 0.1, 1.0),
            (group, "ln1", "scale"): ((n, d), NORM_DTYPE, 0.1, 0.0),
            (group, "ln2", "scale"): ((n, d), NORM_DTYPE, 0.1, 0.0),
        }

    def ffn(path, n, width, lead=()):
        return {
            path + ("w_gate",): ((n,) + lead + (d, width), dt, sd(d), 0.0),
            path + ("w_up",): ((n,) + lead + (d, width), dt, sd(d), 0.0),
            path + ("w_down",): ((n,) + lead + (width, d), dt, sd(width), 0.0),
        }

    out = {
        ("embed", "tok"): ((vp, d), dt, 0.02, 0.0),
        ("embed", "unembed"): ((d, vp), dt, sd(d), 0.0),
        ("final_norm", "scale"): ((d,), NORM_DTYPE, 0.1, 0.0),
    }
    out.update(attn("layers", L - nd))
    out[("layers", "moe", "router")] = (
        (L - nd, d, e["num_experts"]), jnp.float32, ROUTER_LOGIT_STD / math.sqrt(d), 0.0)
    out.update(ffn(("layers", "moe"), L - nd, f, (held,)))
    shape, dtype, _, mean = out[("layers", "moe", "w_down")]
    out[("layers", "moe", "w_down")] = (shape, dtype, sd(f * e["top_k"]), mean)
    out.update(ffn(("layers", "moe", "shared"), L - nd, fs))
    if nd:
        out.update(attn("dense_layers", nd))
        out.update(ffn(("dense_layers", "ffn"), nd, model["first_dense_d_ff"]))
    return out


# --- the plain reference -----------------------------------------------------


def _yarn(model: dict) -> tuple:
    """(inverse frequencies (rope/2,), cos/sin factor, softmax scale)."""
    m, rs = model["mla"], model["rope_scaling"]
    dim, base = m["qk_rope_head_dim"], model["rope_theta"]
    inv = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = 1.0 / math.sqrt(m["qk_nope_head_dim"] + dim)
    if not rs:
        return inv, 1.0, scale
    s = rs["factor"]
    ms = lambda k: 1.0 if s <= 1 else 0.1 * k * math.log(s) + 1.0
    turn = lambda rot: dim * math.log(rs["original_max_position_embeddings"]
                                      / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(turn(rs["beta_fast"])), 0)
    high = min(math.ceil(turn(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = inv / s * ramp + inv * (1.0 - ramp)
    if rs.get("mscale_all_dim"):
        scale *= ms(rs["mscale_all_dim"]) ** 2
    return inv, ms(rs.get("mscale", 1.0)) / ms(rs.get("mscale_all_dim", 0.0)), scale


def _rope(x: jax.Array, inv: np.ndarray, factor: float) -> jax.Array:
    """x: (T, heads, dim), positions 0..T-1, rotate-half."""
    ang = np.arange(x.shape[0])[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang) * factor, jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang) * factor, jnp.float32)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _swiglu(h, w, mode):
    return _mm(jax.nn.silu(_mm(h, w["w_gate"], mode)) * _mm(h, w["w_up"], mode),
               w["w_down"], mode)


def _mla(x, a, model, mode):
    """Expanded-form causal MLA over one sequence x (T, d)."""
    m, eps = model["mla"], model["norm_eps"]
    T, d = x.shape
    nh, nope, rope, r = (model["n_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["kv_lora_rank"])
    inv, factor, scale = _yarn(model)
    cq = _rms(_mm(x, a["w_dq"], mode), a["q_norm_scale"], eps)
    q = _mm(cq, a["w_uq"].reshape(cq.shape[-1], -1), mode).reshape(T, nh, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], inv, factor)
    kv = _mm(x, a["w_dkv"], mode)
    c = _rms(kv[:, :r], a["kv_norm_scale"], eps)
    k_pe = _rope(kv[:, None, r:], inv, factor)[:, 0]
    k_nope = _mm(c, a["w_uk"].reshape(r, -1), mode).reshape(T, nh, nope)
    v = _mm(c, a["w_uv"].reshape(r, -1), mode).reshape(T, nh, -1)
    causal = np.tril(np.ones((T, T), bool))

    def heads(blk):
        qn, qp, kn, vv = blk                                    # (T, hb, .)
        s = (jnp.einsum("qnh,knh->nqk", qn, kn, precision=HIGHEST)
             + jnp.einsum("qnh,kh->nqk", qp, k_pe, precision=HIGHEST)) * scale
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("nqk,knh->qnh", jax.nn.softmax(s, -1), vv, precision=HIGHEST)

    hb = math.gcd(nh, HEAD_BLOCK)
    split = lambda t: jnp.moveaxis(t.reshape(T, nh // hb, hb, -1), 1, 0)
    o = jax.lax.map(heads, (split(q_nope), split(q_pe), split(k_nope), split(v)))
    o = jnp.moveaxis(o, 0, 1).reshape(T, -1)                    # heads in order
    return _mm(o, a["w_o"].reshape(-1, d), mode)


def _route(probs: jax.Array, moe: dict) -> tuple:
    """group_limited_greedy: (gates, ids) (T, top_k)."""
    T, E = probs.shape
    G = moe["n_group"]
    scores = probs
    if G > 1:
        best = probs.reshape(T, G, E // G).max(-1)
        _, groups = jax.lax.top_k(best, moe["topk_group"])
        kept = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], groups].set(True)
        scores = jnp.where(jnp.repeat(kept, E // G, axis=1), probs, 0.0)
    gates, ids = jax.lax.top_k(scores, moe["top_k"])
    if moe["norm_topk_prob"]:
        return gates / gates.sum(-1, keepdims=True), ids
    return gates * moe["routed_scaling_factor"], ids


def _moe(h, w, moe, mode):
    """The held experts' part plus the shared experts, h (T, d)."""
    first, count = _held(moe)
    probs = jax.nn.softmax(_mm(h, w["router"], mode), -1)
    gates, ids = _route(probs, moe)
    dense = jnp.zeros(probs.shape, jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], ids].set(gates)[:, first:first + count]

    def expert(carry, e):
        we = jax.tree.map(lambda t: t[e], {k: w[k] for k in ("w_gate", "w_up", "w_down")})
        return carry + dense[:, e, None] * _swiglu(h, we, mode), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(count))
    return routed + _swiglu(h, w["shared"], mode)


def _layer(x, stack, i, *, model, mode, kind):
    """Layer ``i`` (traced) of ``stack`` on one sequence x (T, d)."""
    lp = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(t, i, keepdims=False), stack)
    lp = jax.tree.map(lambda t: t.astype(jnp.float32), lp)
    x = x + _mla(_norm(x, lp["ln1"], model), lp["attn"], model, mode)
    h = _norm(x, lp["ln2"], model)
    if kind == "dense":
        return x + _swiglu(h, lp["ffn"], mode)
    return x + _moe(h, lp["moe"], model["moe"], mode)


def _freeze(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return tuple(v) if isinstance(v, list) else v


def _thaw(v):
    if isinstance(v, tuple) and v and all(isinstance(x, tuple) and len(x) == 2
                                          and isinstance(x[0], str) for x in v):
        return {k: _thaw(x) for k, x in v}
    return v


@functools.lru_cache(maxsize=None)
def _fns(frozen: tuple, mode: str):
    model = _thaw(frozen)
    layer = {kind: jax.jit(functools.partial(_layer, model=model, mode=mode, kind=kind))
             for kind in ("dense", "moe")}

    @jax.jit
    def logits(embed_params, final_norm, x, cols):
        h = _norm(x[cols], final_norm, model)                         # (N, d)
        return _mm(h, embed_params["unembed"][:, :model["vocab"]], mode)

    return layer, logits


def logits_at(params: dict, model: dict, tokens: np.ndarray, rows: np.ndarray,
              cols: np.ndarray, mode: str = "float32", block: int = 1) -> jax.Array:
    """Logits (N, vocab) at positions ``(rows[i], cols[i])`` of the token
    matrix ``tokens`` (K, T), each from the causal prefix up to it.
    ``rows`` is non-decreasing; one sequence at a time (``block`` is
    accepted for the common signature)."""
    if np.any(np.diff(rows) < 0):
        raise ValueError("rows must be non-decreasing")
    layer, logits = _fns(_freeze(model), mode)
    nd = model["first_dense_layers"]
    out = []
    with jax.default_matmul_precision("highest"):
        for k in np.unique(rows):
            c = cols[rows == k]
            x = params["embed"]["tok"][jnp.asarray(tokens[k])].astype(jnp.float32)
            for i in range(model["n_layers"]):
                if i < nd:
                    x = layer["dense"](x, params["dense_layers"], jnp.int32(i))
                else:
                    x = layer["moe"](x, params["layers"], jnp.int32(i - nd))
            n = len(c)
            padded = np.zeros(-(-n // ROWS) * ROWS, np.int32)
            padded[:n] = c
            out.append(logits(params["embed"], params["final_norm"], x,
                              jnp.asarray(padded))[:n])
    return jnp.concatenate(out)


# --- work counts -------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Shapes:
    """The sizes of a DeepSeek-V2 share that the counts depend on."""

    n_layers: int
    dense_layers: int
    d_model: int
    n_heads: int
    kv_lora_rank: int
    q_lora_rank: int
    nope: int
    rope: int
    v_head_dim: int
    dense_d_ff: int
    d_ff_expert: int
    d_ff_shared: int
    n_experts: int
    held: int
    top_k: int
    vocab: int
    dtype_bytes: int

    @classmethod
    def from_model(cls, model: dict) -> "Shapes":
        """From the ``model`` section of a configuration file."""
        m, e = model["mla"], model["moe"]
        return cls(
            n_layers=model["n_layers"], dense_layers=model["first_dense_layers"],
            d_model=model["d_model"], n_heads=model["n_heads"],
            kv_lora_rank=m["kv_lora_rank"], q_lora_rank=m["q_lora_rank"],
            nope=m["qk_nope_head_dim"], rope=m["qk_rope_head_dim"],
            v_head_dim=m["v_head_dim"], dense_d_ff=model["first_dense_d_ff"],
            d_ff_expert=e["d_ff_expert"],
            d_ff_shared=e["d_ff_shared"] * e["num_shared_experts"],
            n_experts=e["num_experts"], held=_held(e)[1], top_k=e["top_k"],
            vocab=model["vocab"], dtype_bytes=DTYPE_BYTES[model["dtype"]],
        )

    @property
    def moe_layers(self) -> int:
        return self.n_layers - self.dense_layers

    @property
    def mla_params(self) -> int:
        """One layer's MLA projections."""
        d, nh, r, qr = self.d_model, self.n_heads, self.kv_lora_rank, self.q_lora_rank
        return (d * qr + qr * nh * (self.nope + self.rope) + d * (r + self.rope)
                + r * nh * self.nope + r * nh * self.v_head_dim + nh * self.v_head_dim * d)

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.d_ff_expert

    @property
    def router_params(self) -> int:
        return self.d_model * self.n_experts

    @property
    def shared_params(self) -> int:
        return 3 * self.d_model * self.d_ff_shared

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def norm_params(self) -> int:
        """RMSNorm scales: two per layer, the final one, the two latent norms."""
        return (self.n_layers * (2 * self.d_model + self.kv_lora_rank + self.q_lora_rank)
                + self.d_model)

    @property
    def cache_bytes_per_position(self) -> int:
        """Latent and rope key of one position over all layers."""
        return self.n_layers * (self.kv_lora_rank + self.rope) * self.dtype_bytes

    @property
    def routed_tokens_per_token(self) -> float:
        """Held-expert assignments a token makes on average over the router."""
        return self.top_k * self.held / self.n_experts

    def param_count(self) -> int:
        """Every parameter: embedding, head, layers, norms."""
        moe = self.held * self.expert_params + self.shared_params + self.router_params
        return (2 * self.head_params + self.n_layers * self.mla_params
                + self.dense_layers * 3 * self.d_model * self.dense_d_ff
                + self.moe_layers * moe + self.norm_params)

    def _weights_bytes(self, tokens: int) -> int:
        """Every weight once (the router in float32, as the engine holds it),
        and the embedding rows of ``tokens`` input tokens."""
        mats = (self.head_params + self.n_layers * self.mla_params
                + self.dense_layers * 3 * self.d_model * self.dense_d_ff
                + self.moe_layers * (self.held * self.expert_params + self.shared_params)
                + tokens * self.d_model)
        return (mats * self.dtype_bytes + self.moe_layers * self.router_params * 4
                + self.norm_params * NORM_BYTES)

    def _dense_flops(self, tokens: float) -> float:
        """Matrix FLOPs of ``tokens`` tokens outside attention scores and
        the routed experts (MLA projections, dense FFN, shared experts,
        router); the head is separate."""
        per = (self.n_layers * self.mla_params
               + self.dense_layers * 3 * self.d_model * self.dense_d_ff
               + self.moe_layers * (self.shared_params + self.router_params))
        return 2 * tokens * per

    def _routed_flops(self, tokens: float) -> float:
        return 2 * tokens * self.routed_tokens_per_token * self.moe_layers * self.expert_params

    def mla_decode(self, positions: Iterable[int]) -> tuple[int, int]:
        """(FLOPs, bytes) of the MLA layers of one decode step, absorbed
        form: projections and W_uk / W_uv folded per token, scores and
        latent context over each slot's cached positions and itself; its
        weights, the cache read and the new rows written."""
        positions = list(positions)
        n = len(positions)
        if not n:
            return 0, 0
        keys = sum(p + 1 for p in positions)
        per_key = 2 * self.n_heads * (self.kv_lora_rank + self.rope + self.kv_lora_rank)
        flops = self.n_layers * (2 * n * self.mla_params + per_key * keys)
        cache = self.cache_bytes_per_position
        nbytes = (self.n_layers * self.mla_params * self.dtype_bytes
                  + sum(positions) * cache + n * cache)
        return flops, nbytes

    def moe_decode(self, tokens_here: int, experts_hit: int, live: int) -> tuple[int, int]:
        """(FLOPs, bytes) of the MoE layers of one decode step from its
        routing counts (summed over the MoE layers): the router and shared
        experts over ``live`` tokens, the held experts over the
        ``tokens_here`` assignments, and the weights of the ``experts_hit``
        experts, the shared experts and the router."""
        flops = (2 * live * self.moe_layers * (self.shared_params + self.router_params)
                 + 2 * tokens_here * self.expert_params)
        nbytes = (experts_hit * self.expert_params * self.dtype_bytes
                  + self.moe_layers * (self.shared_params * self.dtype_bytes
                                       + self.router_params * 4))
        return flops, nbytes

    def decode(self, positions: Iterable[int]) -> tuple[int, int]:
        """(FLOPs, bytes) of one decode step whose live slots hold
        ``positions`` cached positions each before the step."""
        positions = list(positions)
        n = len(positions)
        if not n:
            return 0, 0
        keys = sum(p + 1 for p in positions)
        per_key = 2 * self.n_heads * (self.kv_lora_rank + self.rope + self.kv_lora_rank)
        flops = (self._dense_flops(n) + self._routed_flops(n) + 2 * n * self.head_params
                 + self.n_layers * per_key * keys)
        cache = self.cache_bytes_per_position
        nbytes = self._weights_bytes(n) + sum(positions) * cache + n * cache
        return int(flops), nbytes

    def prefill(self, prompt_len: int) -> tuple[int, int]:
        """(FLOPs, bytes) of one prefill of ``prompt_len`` true tokens in
        the expanded form, ending in the last position's logits."""
        p = prompt_len
        pairs = p * (p + 1) // 2
        per_pair = 2 * self.n_heads * (self.nope + self.rope + self.v_head_dim)
        flops = (self._dense_flops(p) + self._routed_flops(p) + 2 * self.head_params
                 + self.n_layers * per_pair * pairs)
        nbytes = self._weights_bytes(p) + p * self.cache_bytes_per_position
        return int(flops), nbytes
