"""Shared neural-net layers (pure JAX, functional, init/apply pairs).

Every layer is a pair of functions: ``init_*`` returning (params, axes) where
``axes`` is a matching pytree of logical-axis strings (see
distributed/sharding.parse_axes), and an apply function taking params
explicitly.  No framework (flax/haiku) — the parameter tree and its sharding
metadata stay fully visible to the launchers and the dry-run.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain, gather_fsdp

Params = dict
Axes = dict


def _dtype(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, in_axis: int = 0) -> jax.Array:
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg, d: Optional[int] = None) -> tuple[Params, Axes]:
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        p = {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}
        a = {"scale": "_", "bias": "_"}
    else:
        p = {"scale": jnp.zeros((d,), jnp.float32)}  # gemma-style (1+scale)
        a = {"scale": "_"}
    return p, a


def apply_norm(p: Params, x: jax.Array, cfg) -> jax.Array:
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * (1.0 + p["scale"])
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, scaling=None) -> jax.Array:
    """Inverse frequencies (head_dim/2,); with ``scaling`` (a
    ``RopeScaling``), YaRN's: the low frequencies divided by its factor,
    the high ones kept, a linear ramp between the dims whose wavelength
    fits ``beta_slow`` and ``beta_fast`` turns into the original context."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta ** exponents)                    # (head_dim/2,)
    if scaling is None:
        return freqs

    def dim_of(rotations):
        return head_dim * math.log(
            scaling.original_max_position_embeddings / (rotations * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(dim_of(scaling.beta_fast)), 0)
    high = min(math.ceil(dim_of(scaling.beta_slow)), head_dim - 1)
    ramp = (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3)
    keep = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    return freqs / scaling.factor * (1.0 - keep) + freqs * keep


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, 1 at no scaling."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq).  ``scaling``:
    YaRN (see :func:`rope_freqs`), whose cos/sin carry the ratio of its
    two mscales."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta, scaling)          # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                   # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None:
        ratio = (yarn_mscale(scaling.factor, scaling.mscale)
                 / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if ratio != 1.0:
            cos, sin = cos * ratio, sin * ratio
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# soft capping (gemma2)
# ---------------------------------------------------------------------------

def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# feed-forward
# ---------------------------------------------------------------------------

def init_ffn(key, cfg, d_ff: Optional[int] = None) -> tuple[Params, Axes]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = _dtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_gate": dense_init(k1, (d, f), dt),
        "w_up": dense_init(k2, (d, f), dt),
        "w_down": dense_init(k3, (f, d), dt),
    }
    a = {"w_gate": "fsdp mlp", "w_up": "fsdp mlp", "w_down": "mlp fsdp"}
    return p, a


def _act(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    return jax.nn.gelu(x, approximate=True)


def apply_ffn(p: Params, x: jax.Array, cfg) -> jax.Array:
    w_gate = gather_fsdp(p["w_gate"], "fsdp", "mlp", group="ffn")
    w_up = gather_fsdp(p["w_up"], "fsdp", "mlp", group="ffn")
    w_down = gather_fsdp(p["w_down"], "mlp", "fsdp", group="ffn")
    h = _act(x @ w_gate, cfg.activation) * (x @ w_up)
    h = constrain(h, "batch", "seq", "mlp")
    return h @ w_down


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def init_embeddings(key, cfg) -> tuple[Params, Axes]:
    dt = _dtype(cfg)
    v, d = cfg.padded_vocab, cfg.d_model
    k1, k2 = jax.random.split(key)
    p: Params = {"tok": embed_init(k1, (v, d), dt)}
    a: Axes = {"tok": "vocab fsdp"}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(k2, (d, v), dt)
        a["unembed"] = "fsdp vocab"
    return p, a


def embed_tokens(p: Params, tokens: jax.Array, cfg) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0)
    if cfg.name.startswith("gemma2"):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(p: Params, x: jax.Array, cfg) -> jax.Array:
    w = p["tok"].T if cfg.tie_embeddings else p["unembed"]
    logits = (x @ w.astype(x.dtype)).astype(jnp.float32)
    return softcap(logits, cfg.final_softcap)


# ---------------------------------------------------------------------------
# attention (GQA, sliding window, softcap) with optional KV cache
# ---------------------------------------------------------------------------

def init_attention(key, cfg) -> tuple[Params, Axes]:
    d, h = cfg.d_model, cfg.resolved_head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    dt = _dtype(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, (d, nh, h), dt),
        "wk": dense_init(k2, (d, nkv, h), dt),
        "wv": dense_init(k3, (d, nkv, h), dt),
        "wo": dense_init(k4, (nh, h, d), dt, in_axis=0),
    }
    a = {
        "wq": "fsdp heads head_dim",
        "wk": "fsdp kv_heads head_dim",
        "wv": "fsdp kv_heads head_dim",
        "wo": "heads head_dim fsdp",
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((h,), jnp.float32)
        p["k_norm"] = jnp.ones((h,), jnp.float32)
        a["q_norm"] = "_"
        a["k_norm"] = "_"
    return p, a


def _attn_mask(
    q_pos: jax.Array,            # (S,) or (B, S) — per-sequence positions
    kv_pos: jax.Array,           # (T,)
    window,                      # None => full; int or traced int32 otherwise
    kv_len_valid: Optional[jax.Array],   # scalar or (B,)
    causal: bool = True,
) -> jax.Array:
    """(..., q, kv) boolean mask: causal, sliding window, cache length.

    ``q_pos`` may be per-batch (continuous batching: every slot decodes at
    its own offset).  ``window`` may be a traced per-layer value (gemma2's
    local/global alternation runs under one ``lax.scan``).
    """
    qp = q_pos[..., :, None]
    kp = kv_pos[None, :] if q_pos.ndim == 1 else kv_pos[None, None, :]
    if causal:
        m = kp <= qp
    else:
        m = jnp.ones(qp.shape[:-1] + (kv_pos.shape[0],), bool)
    if window is not None:
        m &= kp > qp - window
    if kv_len_valid is not None:
        kv_valid = jnp.asarray(kv_len_valid)
        if kv_valid.ndim == 1 and q_pos.ndim > 1:
            m &= kp < kv_valid[:, None, None]
        else:
            m &= kp < kv_valid
    return m


def attention(
    p: Params,
    x: jax.Array,                     # (B, S, D)
    cfg,
    *,
    positions: jax.Array,             # (B, S)
    layer_window=None,                # None => full causal; int/traced int32
    cache: Optional[dict] = None,     # {"k","v"}: (B, S_max, nkv, hd); "pos"
    causal: bool = True,
    use_flash: bool = False,
    update_cache: bool = True,        # False => deferred append (see below)
) -> tuple[jax.Array, Any]:
    B, S, D = x.shape
    h = cfg.resolved_head_dim
    scale = cfg.attn_logit_scale or (1.0 / math.sqrt(h))

    q = jnp.einsum("bsd,dnh->bsnh", x, gather_fsdp(p["wq"], "fsdp", "heads", "_", group="attn"))
    k = jnp.einsum("bsd,dnh->bsnh", x, gather_fsdp(p["wk"], "fsdp", "kv_heads", "_", group="attn"))
    v = jnp.einsum("bsd,dnh->bsnh", x, gather_fsdp(p["wv"], "fsdp", "kv_heads", "_", group="attn"))
    if cfg.qk_norm:
        q = _rms(q) * p["q_norm"]
        k = _rms(k) * p["k_norm"]
        q, k = q.astype(x.dtype), k.astype(x.dtype)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", "_")
    k = constrain(k, "batch", "seq", "kv_heads", "_")

    kv_valid = None
    if cache is not None and not update_cache:
        # Deferred append: attend against the read-only cache plus the new
        # tokens *without* materializing an updated cache — the caller
        # writes all layers' new K/V after the layer scan (``append_kv``),
        # in place when the cache is donated (the per-layer update inside
        # a scan cannot be elided and costs a full cache copy per step).
        idx = jnp.broadcast_to(jnp.asarray(cache["pos"]), (B,)).astype(jnp.int32)
        out = _sdpa_deferred(
            q, cache["k"], cache["v"], k, v,
            scale=scale,
            softcap_val=cfg.attn_softcap,
            positions=positions,
            window=layer_window,
            kv_valid=idx,
        )
        y = jnp.einsum(
            "bsnh,nhd->bsd", out, gather_fsdp(p["wo"], "heads", "_", "fsdp", group="attn")
        )
        return y, (k, v)
    if cache is not None:
        # decode / incremental: write new k,v at each slot's own offset
        # (pos is (B,) for continuous batching; scalar broadcasts)
        idx = jnp.broadcast_to(jnp.asarray(cache["pos"]), (B,)).astype(jnp.int32)
        upd = lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0, 0))
        ck = jax.vmap(upd)(cache["k"], k.astype(cache["k"].dtype), idx)
        cv = jax.vmap(upd)(cache["v"], v.astype(cache["v"].dtype), idx)
        cache = {"k": ck, "v": cv, "pos": cache["pos"] + S}
        k, v = ck, cv
        kv_pos = jnp.arange(k.shape[1])
        q_pos = positions                      # (B, S)
        kv_valid = idx + S
    else:
        kv_pos = positions[0]
        q_pos = positions[0]

    out = _sdpa(
        q, k, v,
        scale=scale,
        softcap_val=cfg.attn_softcap,
        q_pos=q_pos,
        kv_pos=kv_pos,
        window=layer_window,
        kv_valid=kv_valid,
        causal=causal,
    )
    y = jnp.einsum("bsnh,nhd->bsd", out, gather_fsdp(p["wo"], "heads", "_", "fsdp", group="attn"))
    return y, cache


def _rms(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)


def _sdpa(q, k, v, *, scale, softcap_val, q_pos, kv_pos, window, kv_valid,
          causal=True):
    """Grouped-query scaled dot-product attention, reference path."""
    B, S, NH, H = q.shape
    NKV = k.shape[2]
    G = NH // NKV
    qg = q.reshape(B, S, NKV, G, H)
    logits = jnp.einsum(
        "bsngh,btnh->bngst", qg, k, preferred_element_type=jnp.float32
    )
    logits *= scale
    logits = softcap(logits, softcap_val)
    mask = _attn_mask(q_pos, kv_pos, window, kv_valid, causal)  # (S,T) or (B,S,T)
    if mask.ndim == 2:
        mask = mask[None, None, None]                            # (1,1,1,S,T)
    else:
        mask = mask[:, None, None]                               # (B,1,1,S,T)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngst,btnh->bsngh", probs.astype(v.dtype), v)
    return out.reshape(B, S, NH, H)


def _sdpa_deferred(q, k_cache, v_cache, k_new, v_new, *, scale, softcap_val,
                   positions, window, kv_valid):
    """Two-part attention for deferred cache append.

    Scores against the (read-only) cache and against the new tokens are
    computed separately and softmaxed jointly — equivalent to attending over
    the updated cache, without writing it.
    q: (B,S,NH,H); k_cache/v_cache: (B,T,NKV,H); k_new/v_new: (B,S,NKV,H);
    kv_valid: (B,) number of valid cache entries (== write offset).
    """
    B, S, NH, H = q.shape
    NKV = k_cache.shape[2]
    G = NH // NKV
    # native-dtype dots with f32 accumulation: converting the cache to f32
    # would materialize a 2x-sized copy of the whole cache per layer (the
    # dominant decode traffic; see EXPERIMENTS.md §Perf decode hillclimb)
    qg = q.reshape(B, S, NKV, G, H)

    # part 1: existing cache
    s1 = jnp.einsum(
        "bsngh,btnh->bngst", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) * scale
    s1 = softcap(s1, softcap_val)
    t = jnp.arange(k_cache.shape[1])
    m1 = t[None, None, :] < kv_valid[:, None, None]              # (B,1,T)
    m1 = m1 & (t[None, None, :] <= positions[..., None])
    if window is not None:
        m1 = m1 & (t[None, None, :] > positions[..., None] - window)
    s1 = jnp.where(m1[:, None, None], s1, -1e30)

    # part 2: the new tokens (causal among themselves)
    s2 = jnp.einsum(
        "bsngh,btnh->bngst", qg, k_new, preferred_element_type=jnp.float32
    ) * scale
    s2 = softcap(s2, softcap_val)
    new_pos = kv_valid[:, None] + jnp.arange(S)[None, :]         # (B,S)
    m2 = new_pos[:, None, :] <= positions[..., None]             # (B,S,S)
    if window is not None:
        m2 = m2 & (new_pos[:, None, :] > positions[..., None] - window)
    s2 = jnp.where(m2[:, None, None], s2, -1e30)

    s = jnp.concatenate([s1, s2], axis=-1)
    probs = jax.nn.softmax(s, axis=-1)
    p1, p2 = probs[..., : k_cache.shape[1]], probs[..., k_cache.shape[1]:]
    out = jnp.einsum("bngst,btnh->bsngh", p1.astype(v_cache.dtype), v_cache)
    out += jnp.einsum("bngst,btnh->bsngh", p2.astype(v_new.dtype), v_new)
    return out.reshape(B, S, NH, H)


def append_kv(cache_k, cache_v, new_k, new_v, pos):
    """Write every layer's new K/V into the cache at each slot's offset.

    cache_k/v: (L,B,T,...) (dense: (L,B,T,nkv,hd); latent: (L,B,T,r));
    new_k/v: (L,B,S_new,...) alike; pos: (B,).
    One dynamic-update-slice per slot, over the static slot count, in the
    cache's own dimension order: with the cache donated, XLA updates it in
    place.  (A vmap over the slot axis compiles on TPU to a slot-major
    transpose of the whole cache, a scatter, and a transpose back.)  Each
    offset clamps to ``T - S_new``, as ``dynamic_update_slice`` does."""
    new_k = new_k.astype(cache_k.dtype)
    new_v = new_v.astype(cache_v.dtype)
    for b in range(cache_k.shape[1]):
        at = (0, b, pos[b]) + (0,) * (cache_k.ndim - 3)
        cache_k = jax.lax.dynamic_update_slice(cache_k, new_k[:, b:b + 1], at)
        cache_v = jax.lax.dynamic_update_slice(cache_v, new_v[:, b:b + 1], at)
    return cache_k, cache_v


def cross_attention(p: Params, x: jax.Array, memory: jax.Array, cfg) -> jax.Array:
    """Encoder-decoder cross attention: queries from x, K/V from memory.
    No RoPE on cross keys (positions are heterogeneous across modalities)."""
    B, S, D = x.shape
    T = memory.shape[1]
    h = cfg.resolved_head_dim
    scale = cfg.attn_logit_scale or (1.0 / math.sqrt(h))
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.einsum("btd,dnh->btnh", memory, p["wk"])
    v = jnp.einsum("btd,dnh->btnh", memory, p["wv"])
    out = _sdpa(
        q, k, v,
        scale=scale,
        softcap_val=0.0,
        q_pos=jnp.arange(S),
        kv_pos=jnp.arange(T),
        window=None,
        kv_valid=None,
        causal=False,
    )
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"])
