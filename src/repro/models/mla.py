"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

Queries and keys/values are projected through low-rank *latents*; only the
compressed KV latent (kv_lora_rank) plus a shared RoPE key (qk_rope_head_dim)
are cached at decode time — the architecture's core memory saving.  Both
latents are RMS-normed (``q_a_layernorm``, ``kv_a_layernorm``); RoPE may be
YaRN-scaled (``cfg.rope_scaling``), which also multiplies the softmax scale
by its mscale squared.

Two execution paths:
* **prefill/train** — expand K/V from the latent per token (standard form),
  attending within the given tokens only;
* **decode** (one new token against a cache) — *absorbed* form: W_uk is
  folded into the query so attention runs directly against the latent cache
  (no per-step K expansion).  The cache is read only: the new token's latent
  rows are returned for the caller to write after the layer scan.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain, gather_fsdp

from .layers import _rms, apply_rope, dense_init, yarn_mscale


def init_mla(key, cfg):
    m = cfg.mla
    d, nh = cfg.d_model, cfg.n_heads
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    p = {}
    a = {}
    if m.q_lora_rank:
        p["w_dq"] = dense_init(ks[0], (d, m.q_lora_rank), dt)
        p["q_norm_scale"] = jnp.ones((m.q_lora_rank,), jnp.float32)
        p["w_uq"] = dense_init(ks[1], (m.q_lora_rank, nh, m.qk_nope_head_dim + m.qk_rope_head_dim), dt)
        a["w_dq"] = "fsdp lora"
        a["q_norm_scale"] = "_"
        a["w_uq"] = "lora heads head_dim"
    else:
        p["w_q"] = dense_init(ks[0], (d, nh, m.qk_nope_head_dim + m.qk_rope_head_dim), dt)
        a["w_q"] = "fsdp heads head_dim"
    p["w_dkv"] = dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dt)
    p["w_uk"] = dense_init(ks[3], (m.kv_lora_rank, nh, m.qk_nope_head_dim), dt)
    p["w_uv"] = dense_init(ks[4], (m.kv_lora_rank, nh, m.v_head_dim), dt)
    p["w_o"] = dense_init(ks[5], (nh, m.v_head_dim, d), dt)
    p["kv_norm_scale"] = jnp.ones((m.kv_lora_rank,), jnp.float32)
    a.update({
        "w_dkv": "fsdp lora",
        "w_uk": "lora heads head_dim",
        "w_uv": "lora heads head_dim",
        "w_o": "heads head_dim fsdp",
        "kv_norm_scale": "_",
    })
    return p, a


def softmax_scale(cfg) -> float:
    m, rs = cfg.mla, cfg.rope_scaling
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def _project_latents(p, x, cfg, positions):
    """Common front: query heads + (latent, shared rope key)."""
    m = cfg.mla
    if "w_dq" in p:
        cq = x @ gather_fsdp(p["w_dq"], "fsdp", "lora", group="attn")
        cq = (_rms(cq) * p["q_norm_scale"]).astype(x.dtype)
        q = jnp.einsum("bsr,rnh->bsnh", cq, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dnh->bsnh", x, gather_fsdp(p["w_q"], "fsdp", "heads", "_", group="attn"))
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)

    ckv_full = x @ gather_fsdp(p["w_dkv"], "fsdp", "lora", group="attn")                                # (B,S,lora+rope)
    c_kv, k_rope = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    c_kv = (_rms(c_kv) * p["kv_norm_scale"]).astype(x.dtype)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        cfg.rope_scaling)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_attention(
    p,
    x: jax.Array,                       # (B,S,D)
    cfg,
    *,
    positions: jax.Array,               # (B,S)
    cache: Optional[dict] = None,       # {"ckv": (B,T,lora), "krope": (B,T,rope), "pos": (B,)}
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Returns ``(y, (c_kv, k_rope))``: the output and the given tokens'
    latent rows, which the caller writes into its cache.

    Without a cache, or with more than one token (a prompt from position
    0: the serving engine's prefill), the tokens attend among themselves in
    the expanded form.  One token with a cache attends, absorbed, to the
    cache's first ``pos`` positions and to itself."""
    B, S, _ = x.shape
    scale = softmax_scale(cfg)
    q_nope, q_rope, c_kv, k_rope = _project_latents(p, x, cfg, positions)
    q_nope = constrain(q_nope, "batch", "seq", "heads", "_")
    w_o = gather_fsdp(p["w_o"], "heads", "_", "fsdp", group="attn")

    if cache is None or S > 1:
        # standard (expanded) form, within the given tokens
        k_nope = jnp.einsum("btr,rnh->btnh", c_kv, p["w_uk"])
        v = jnp.einsum("btr,rnh->btnh", c_kv, p["w_uv"])
        logits = (
            jnp.einsum("bsnh,btnh->bnst", q_nope.astype(jnp.float32), k_nope.astype(jnp.float32))
            + jnp.einsum("bsnh,bth->bnst", q_rope.astype(jnp.float32), k_rope.astype(jnp.float32))
        ) * scale
        mask = positions[:, :, None] >= positions[:, None, :]          # (B,S,T)
        logits = jnp.where(mask[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bnst,btnh->bsnh", probs.astype(v.dtype), v)
        y = jnp.einsum("bsnh,nhd->bsd", out, w_o)
        return y, (c_kv, k_rope)

    # ---- absorbed decode: attention directly against the latent cache ----
    ckv_c, krope_c = cache["ckv"], cache["krope"]
    idx = jnp.broadcast_to(jnp.asarray(cache["pos"]), (B,)).astype(jnp.int32)
    # fold W_uk into the query: q_lat (B,1,N,lora); native-type dots with
    # float32 accumulation (a float32 copy of the cache would double its read)
    q_lat = jnp.einsum("bsnh,rnh->bsnr", q_nope, p["w_uk"])
    f32 = jnp.float32
    s_cache = (
        jnp.einsum("bsnr,btr->bnst", q_lat, ckv_c, preferred_element_type=f32)
        + jnp.einsum("bsnh,bth->bnst", q_rope, krope_c, preferred_element_type=f32)
    ) * scale
    t = jnp.arange(ckv_c.shape[1])
    s_cache = jnp.where((t[None, :] < idx[:, None])[:, None, None], s_cache, -1e30)
    s_self = (
        jnp.einsum("bsnr,bsr->bns", q_lat, c_kv, preferred_element_type=f32)
        + jnp.einsum("bsnh,bsh->bns", q_rope, k_rope, preferred_element_type=f32)
    )[..., None] * scale                                              # (B,N,1,1)
    probs = jax.nn.softmax(jnp.concatenate([s_cache, s_self], axis=-1), axis=-1)
    p_cache, p_self = probs[..., :-1], probs[..., -1:]
    # attend in latent space, then expand through W_uv
    ctx_lat = jnp.einsum("bnst,btr->bsnr", p_cache.astype(x.dtype), ckv_c).astype(f32)
    ctx_lat += jnp.einsum("bnst,btr->bsnr", p_self, c_kv.astype(f32))
    out = jnp.einsum("bsnr,rnh->bsnh", ctx_lat.astype(x.dtype), p["w_uv"])
    y = jnp.einsum("bsnh,nhd->bsd", out, w_o)
    return y, (c_kv, k_rope)
