"""Model assembly for all assigned architecture families.

One functional API across families (dense / moe / vlm / hybrid / ssm / audio):

    params, axes = init_model(key, cfg)
    logits, aux  = forward(params, batch, cfg)                 # full sequence
    cache        = init_cache(cfg, batch_size, max_len)        # decode state
    logits, cache= decode_step(params, cache, tokens, pos, cfg)

Layer stacks are ``lax.scan`` over stacked parameters (bounded HLO size so
the 512-device dry-run compiles quickly); heterogeneous stacks (xLSTM's
sLSTM/mLSTM alternation) unroll since their parameter structures differ.

``abstract_model(cfg)`` returns (ShapeDtypeStruct tree, axes tree) without
allocating — the dry-run path.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain

from . import layers as L
from .mla import init_mla, mla_attention
from .moe import apply_moe, init_moe
from .ssm import init_mamba2, mamba2_block, ssm_dims
from .xlstm import init_mlstm, init_slstm, mlstm_block, slstm_block

Params = Any


# ---------------------------------------------------------------------------
# per-family layer blocks
# ---------------------------------------------------------------------------

def _init_attn_block(key, cfg, *, dense_ffn: bool = False):
    """One attention + FFN block; ``dense_ffn``: a leading dense layer of a
    MoE model (an FFN of width ``first_dense_d_ff`` in the experts' place)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if cfg.mla is not None:
        attn_p, attn_a = init_mla(k1, cfg)
    else:
        attn_p, attn_a = L.init_attention(k1, cfg)
    n1p, n1a = L.init_norm(cfg)
    n2p, n2a = L.init_norm(cfg)
    p = {"attn": attn_p, "ln1": n1p, "ln2": n2p}
    a = {"attn": attn_a, "ln1": n1a, "ln2": n2a}
    if cfg.post_attn_norm:
        n3p, n3a = L.init_norm(cfg)
        n4p, n4a = L.init_norm(cfg)
        p["ln_post_attn"], a["ln_post_attn"] = n3p, n3a
        p["ln_post_ffn"], a["ln_post_ffn"] = n4p, n4a
    if dense_ffn:
        ffn_p, ffn_a = L.init_ffn(k3, cfg, cfg.first_dense_d_ff)
        p["ffn"], a["ffn"] = ffn_p, ffn_a
    elif cfg.moe is not None:
        moe_p, moe_a = init_moe(k2, cfg)
        p["moe"], a["moe"] = moe_p, moe_a
        if cfg.d_ff:  # arctic: parallel dense residual branch
            ffn_p, ffn_a = L.init_ffn(k3, cfg)
            p["ffn"], a["ffn"] = ffn_p, ffn_a
    else:
        ffn_p, ffn_a = L.init_ffn(k3, cfg)
        p["ffn"], a["ffn"] = ffn_p, ffn_a
    return p, a


def _attn_ffn_block(
    lp, x, cfg, *, positions, window, cache=None, causal=True
):
    """Standard pre-norm transformer block; returns (x, new_cache, aux)."""
    h = L.apply_norm(lp["ln1"], x, cfg)
    if cfg.mla is not None:
        with jax.named_scope("mla"):
            attn_out, _ = mla_attention(lp["attn"], h, cfg, positions=positions)
        new_cache = None
    else:
        attn_out, new_cache = L.attention(
            lp["attn"], h, cfg, positions=positions, layer_window=window, cache=cache
        )
    if cfg.post_attn_norm:
        attn_out = L.apply_norm(lp["ln_post_attn"], attn_out, cfg)
    x = x + attn_out
    x = constrain(x, "batch", "seq", "embed")

    h = L.apply_norm(lp["ln2"], x, cfg)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in lp:
        with jax.named_scope("moe"):
            moe_out, aux = apply_moe(lp["moe"], h, cfg)
        if "ffn" in lp:  # arctic dense residual branch in parallel
            moe_out = moe_out + L.apply_ffn(lp["ffn"], h, cfg)
        ffn_out = moe_out
    else:
        ffn_out = L.apply_ffn(lp["ffn"], h, cfg)
    if cfg.post_attn_norm:
        ffn_out = L.apply_norm(lp["ln_post_ffn"], ffn_out, cfg)
    x = x + ffn_out
    return constrain(x, "batch", "seq", "embed"), new_cache, aux


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_model(key, cfg):
    keys = jax.random.split(key, 8)
    p: dict = {}
    a: dict = {}
    emb_p, emb_a = L.init_embeddings(keys[0], cfg)
    p["embed"], a["embed"] = emb_p, emb_a
    nf_p, nf_a = L.init_norm(cfg)
    p["final_norm"], a["final_norm"] = nf_p, nf_a

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        n_dense = cfg.first_dense_layers
        lp, la = _stacked_layers(keys[1], cfg, cfg.n_layers - n_dense, _init_attn_block)
        p["layers"], a["layers"] = lp, la
        if n_dense:
            dp, da = _stacked_layers(
                keys[4], cfg, n_dense,
                functools.partial(_init_attn_block, dense_ffn=True))
            p["dense_layers"], a["dense_layers"] = dp, da
        if fam == "vlm":
            k1, k2 = jax.random.split(keys[2])
            dt = jnp.dtype(cfg.dtype)
            p["projector"] = {
                "w1": L.dense_init(k1, (cfg.vision_dim, cfg.d_model), dt),
                "w2": L.dense_init(k2, (cfg.d_model, cfg.d_model), dt),
            }
            a["projector"] = {"w1": "_ fsdp", "w2": "fsdp fsdp"}
    elif fam == "hybrid":
        lp, la = _stacked_layers(keys[1], cfg, cfg.n_layers, _init_mamba_block)
        p["layers"], a["layers"] = lp, la
        sp, sa = _init_attn_block(keys[2], cfg)   # the *shared* attention block
        p["shared_attn"], a["shared_attn"] = sp, sa
    elif fam == "ssm":  # xLSTM
        lps, las = [], []
        lkeys = jax.random.split(keys[1], cfg.n_layers)
        for i in range(cfg.n_layers):
            if i in cfg.xlstm.slstm_at:
                bp, ba = _init_xlstm_layer(lkeys[i], cfg, kind="slstm")
            else:
                bp, ba = _init_xlstm_layer(lkeys[i], cfg, kind="mlstm")
            lps.append(bp)
            las.append(ba)
        p["layers"], a["layers"] = lps, las
    elif fam == "audio":
        ep, ea = _stacked_layers(keys[1], cfg, cfg.n_enc_layers, _init_enc_block)
        dp, da = _stacked_layers(keys[2], cfg, cfg.n_layers, _init_dec_block)
        p["encoder"], a["encoder"] = ep, ea
        p["decoder"], a["decoder"] = dp, da
        ne_p, ne_a = L.init_norm(cfg)
        p["enc_final_norm"], a["enc_final_norm"] = ne_p, ne_a
        k1 = keys[3]
        dt = jnp.dtype(cfg.dtype)
        p["frontend_proj"] = {"w": L.dense_init(k1, (cfg.audio_dim, cfg.d_model), dt)}
        a["frontend_proj"] = {"w": "_ fsdp"}
    else:
        raise ValueError(f"unknown family {fam}")
    return p, a


def _stacked_layers(key, cfg, n, init_one):
    keys = jax.random.split(key, max(n, 1))
    ps, as_ = [], []
    for i in range(n):
        bp, ba = init_one(keys[i], cfg)
        ps.append(bp)
        as_.append(ba)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ps)
    axes = jax.tree_util.tree_map(lambda s: "layers " + s, as_[0])
    return stacked, axes


def _init_mamba_block(key, cfg):
    k1, _ = jax.random.split(key)
    mp, ma = init_mamba2(k1, cfg)
    np_, na = L.init_norm(cfg)
    return {"mamba": mp, "ln": np_}, {"mamba": ma, "ln": na}


def _init_xlstm_layer(key, cfg, *, kind):
    np_, na = L.init_norm(cfg)
    if kind == "slstm":
        bp, ba = init_slstm(key, cfg)
    else:
        bp, ba = init_mlstm(key, cfg)
    return {"ln": np_, "cell": bp}, {"ln": na, "cell": ba}


def _xlstm_kind(cfg, i: int) -> str:
    return "slstm" if i in cfg.xlstm.slstm_at else "mlstm"


def _init_enc_block(key, cfg):
    k1, k2 = jax.random.split(key)
    ap, aa = L.init_attention(k1, cfg)
    fp, fa = L.init_ffn(k2, cfg)
    n1p, n1a = L.init_norm(cfg)
    n2p, n2a = L.init_norm(cfg)
    return (
        {"attn": ap, "ffn": fp, "ln1": n1p, "ln2": n2p},
        {"attn": aa, "ffn": fa, "ln1": n1a, "ln2": n2a},
    )


def _init_dec_block(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    sp, sa = L.init_attention(k1, cfg)
    cp, ca = L.init_attention(k2, cfg)
    fp, fa = L.init_ffn(k3, cfg)
    norms_p, norms_a = {}, {}
    for nm in ("ln1", "ln2", "ln3"):
        np_, na = L.init_norm(cfg)
        norms_p[nm], norms_a[nm] = np_, na
    return (
        {"self_attn": sp, "cross_attn": cp, "ffn": fp, **norms_p},
        {"self_attn": sa, "cross_attn": ca, "ffn": fa, **norms_a},
    )


def abstract_model(cfg):
    """(ShapeDtypeStruct params, axes) without allocation — dry-run path."""
    axes_box = {}

    def build(key):
        p, a = init_model(key, cfg)
        axes_box["a"] = a
        return p

    shapes = jax.eval_shape(build, jax.random.key(0))
    return shapes, axes_box["a"]


# ---------------------------------------------------------------------------
# forward (full-sequence; training & prefill-style eval)
# ---------------------------------------------------------------------------

def _remat_policy(cfg):
    """Remat policy: 'full' recomputes everything in the backward pass;
    'dots' saves matmul outputs (checkpoint_dots) so the quadratic attention
    scores and FFN GEMMs are not recomputed — trades activation memory for
    the dominant compute term (see EXPERIMENTS.md §Perf, deepseek hillclimb).
    """
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


def _window_schedule(cfg) -> Optional[jax.Array]:
    """Per-layer attention window: gemma2 alternates local / global."""
    if not cfg.local_global_pattern or not cfg.sliding_window:
        return None
    idx = jnp.arange(cfg.n_layers)
    is_global = (idx % cfg.local_global_pattern) == (cfg.local_global_pattern - 1)
    return jnp.where(is_global, jnp.int32(2**30), jnp.int32(cfg.sliding_window))


def _embed_input(p, batch, cfg):
    """Token (+modality stub) embedding; returns (x, positions)."""
    tokens = batch["tokens"]
    x = L.embed_tokens(p["embed"], tokens, cfg)
    if cfg.family == "vlm":
        ve = batch["vision_embeds"].astype(x.dtype)          # (B, T_img, vis_d)
        proj = jax.nn.gelu(ve @ p["projector"]["w1"]) @ p["projector"]["w2"]
        x = jnp.concatenate([proj, x], axis=1)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return x, positions


def forward(p, batch, cfg):
    """Full-sequence forward.  Returns (logits, aux) where aux holds router
    losses etc.  batch keys per family: tokens [+ vision_embeds | frames]."""
    fam = cfg.family
    if fam == "audio":
        return _forward_encdec(p, batch, cfg)

    x, positions = _embed_input(p, batch, cfg)
    aux_total = jnp.zeros((), jnp.float32)

    if fam in ("dense", "moe", "vlm"):
        windows = _window_schedule(cfg)
        if windows is None:

            def body(x, lp):
                x, _, aux = _attn_ffn_block(lp, x, cfg, positions=positions, window=None)
                return x, aux

        else:

            def body(x, lp_and_w):
                lp, w = lp_and_w
                x, _, aux = _attn_ffn_block(lp, x, cfg, positions=positions, window=w)
                return x, aux

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False, policy=_remat_policy(cfg))
        if "dense_layers" in p:
            x, _ = jax.lax.scan(body, x, p["dense_layers"])
        xs = p["layers"] if windows is None else (p["layers"], windows)
        x, auxs = jax.lax.scan(body, x, xs, unroll=cfg.layer_unroll)
        aux_total = auxs.sum()
    elif fam == "hybrid":
        x, aux_total = _forward_hybrid(p, x, cfg, positions)
    elif fam == "ssm":
        for i, lp in enumerate(p["layers"]):
            x = _xlstm_layer(lp, x, cfg, kind=_xlstm_kind(cfg, i))
    else:
        raise ValueError(fam)

    x = L.apply_norm(p["final_norm"], x, cfg)
    logits = L.unembed(p["embed"], x, cfg)
    logits = constrain(logits, "batch", "seq", "vocab")
    return logits, {"aux_loss": aux_total}


def _xlstm_layer(lp, x, cfg, *, kind, cache=None, return_cache=False):
    h = L.apply_norm(lp["ln"], x, cfg)
    if kind == "slstm":
        out, new_cache = slstm_block(lp["cell"], h, cfg, cache=cache)
    else:
        out, new_cache = mlstm_block(lp["cell"], h, cfg, cache=cache)
    if return_cache:
        return x + out, new_cache
    return x + out


def _forward_hybrid(p, x, cfg, positions):
    """Zamba2: scan over Mamba2 layers; shared attention block every k."""
    every = cfg.hybrid_attn_every
    idxs = jnp.arange(cfg.n_layers)

    def body(x, inp):
        lp, i = inp
        h = L.apply_norm(lp["ln"], x, cfg)
        out, _ = mamba2_block(lp["mamba"], h, cfg)
        x = x + out

        def with_attn(x):
            y, _, _ = _attn_ffn_block(
                p["shared_attn"], x, cfg, positions=positions, window=None
            )
            return y

        x = jax.lax.cond((i % every) == (every - 1), with_attn, lambda x: x, x)
        return x, jnp.zeros((), jnp.float32)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False, policy=_remat_policy(cfg))
    x, _ = jax.lax.scan(body, x, (p["layers"], idxs), unroll=cfg.layer_unroll)
    return x, jnp.zeros((), jnp.float32)


def _forward_encdec(p, batch, cfg):
    frames = batch["frames"].astype(jnp.dtype(cfg.dtype))    # (B,T,audio_dim)
    enc_x = frames @ p["frontend_proj"]["w"]
    B, T = enc_x.shape[:2]
    enc_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def enc_body(x, lp):
        h = L.apply_norm(lp["ln1"], x, cfg)
        o, _ = L.attention(lp["attn"], h, cfg, positions=enc_pos, causal=False)
        x = x + o
        h = L.apply_norm(lp["ln2"], x, cfg)
        x = x + L.apply_ffn(lp["ffn"], h, cfg)
        return constrain(x, "batch", "seq", "embed"), None

    if cfg.remat:
        enc_body = jax.checkpoint(enc_body, prevent_cse=False, policy=_remat_policy(cfg))
    enc_x, _ = jax.lax.scan(enc_body, enc_x, p["encoder"], unroll=cfg.enc_unroll)
    memory = L.apply_norm(p["enc_final_norm"], enc_x, cfg)

    tokens = batch["tokens"]
    x = L.embed_tokens(p["embed"], tokens, cfg)
    Bd, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (Bd, S))

    def dec_body(x, lp):
        h = L.apply_norm(lp["ln1"], x, cfg)
        o, _ = L.attention(lp["self_attn"], h, cfg, positions=positions)
        x = x + o
        h = L.apply_norm(lp["ln2"], x, cfg)
        o = L.cross_attention(lp["cross_attn"], h, memory, cfg)
        x = x + o
        h = L.apply_norm(lp["ln3"], x, cfg)
        x = x + L.apply_ffn(lp["ffn"], h, cfg)
        return constrain(x, "batch", "seq", "embed"), None

    if cfg.remat:
        dec_body = jax.checkpoint(dec_body, prevent_cse=False, policy=_remat_policy(cfg))
    x, _ = jax.lax.scan(dec_body, x, p["decoder"], unroll=cfg.layer_unroll)
    x = L.apply_norm(p["final_norm"], x, cfg)
    logits = L.unembed(p["embed"], x, cfg)
    return constrain(logits, "batch", "seq", "vocab"), {"aux_loss": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
# decode: cache init + single-step
# ---------------------------------------------------------------------------

def init_cache(cfg, batch_size: int, max_len: int, memory_len: int = 0,
               per_slot: bool = True):
    """Allocate (or abstractly shape) the per-architecture decode state.

    ``per_slot=True`` gives every batch slot its own write offset
    (continuous batching).  ``per_slot=False`` uses ONE scalar offset for the
    whole batch (synchronized batch decode): the cache append is then a
    single dynamic-update-slice that XLA elides in place under donation —
    the memory-term win of the decode hillclimb (EXPERIMENTS.md §Perf).
    """
    dt = jnp.dtype(cfg.dtype)
    B, Lc = batch_size, cfg.n_layers
    pos0 = jnp.zeros((B,), jnp.int32) if per_slot else jnp.zeros((), jnp.int32)
    fam = cfg.family
    if fam in ("dense", "vlm") or (fam == "moe" and cfg.mla is None):
        h = cfg.resolved_head_dim
        return {
            "k": jnp.zeros((Lc, B, max_len, cfg.n_kv_heads, h), dt),
            "v": jnp.zeros((Lc, B, max_len, cfg.n_kv_heads, h), dt),
            "pos": pos0,
        }
    if fam == "moe":  # MLA latent cache
        m = cfg.mla
        return {
            "ckv": jnp.zeros((Lc, B, max_len, m.kv_lora_rank), dt),
            "krope": jnp.zeros((Lc, B, max_len, m.qk_rope_head_dim), dt),
            "pos": pos0,
        }
    if fam == "hybrid":
        s = cfg.ssm
        d_inner, H, conv_ch = ssm_dims(cfg)
        n_apps = (cfg.n_layers + cfg.hybrid_attn_every - 1) // cfg.hybrid_attn_every
        h = cfg.resolved_head_dim
        return {
            "ssm_h": jnp.zeros((Lc, B, H, s.head_dim, s.state_dim), jnp.float32),
            "conv": jnp.zeros((Lc, B, s.conv_width - 1, conv_ch), dt),
            "attn_k": jnp.zeros((n_apps, B, max_len, cfg.n_kv_heads, h), dt),
            "attn_v": jnp.zeros((n_apps, B, max_len, cfg.n_kv_heads, h), dt),
            "pos": pos0,
        }
    if fam == "ssm":  # xLSTM: per-layer heterogeneous state, python list
        H, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
        pf = cfg.xlstm.proj_factor
        d_up = int(cfg.d_model * pf)
        hd_up = d_up // H
        caches = []
        for i in range(cfg.n_layers):
            if _xlstm_kind(cfg, i) == "slstm":
                caches.append({
                    "c": jnp.zeros((B, cfg.d_model), jnp.float32),
                    "n": jnp.ones((B, cfg.d_model), jnp.float32),
                    "m": jnp.zeros((B, cfg.d_model), jnp.float32),
                    "h": jnp.zeros((B, cfg.d_model), dt),
                })
            else:
                caches.append({
                    "C": jnp.zeros((B, H, hd_up, hd_up), jnp.float32),
                    "n": jnp.zeros((B, H, hd_up), jnp.float32),
                    "m": jnp.full((B, H), -1e30, jnp.float32),
                })
        return {"layers": caches, "pos": pos0}
    if fam == "audio":
        h = cfg.resolved_head_dim
        return {
            "k": jnp.zeros((Lc, B, max_len, cfg.n_kv_heads, h), dt),
            "v": jnp.zeros((Lc, B, max_len, cfg.n_kv_heads, h), dt),
            "memory": jnp.zeros((B, memory_len, cfg.d_model), dt),
            "pos": pos0,
        }
    raise ValueError(fam)


def cache_axes(cfg, per_slot: bool = True):
    """Logical-axis strings matching :func:`init_cache`'s structure (for the
    dry-run's NamedShardings; see distributed/sharding.parse_axes)."""
    fam = cfg.family
    pos_ax = "batch" if per_slot else ""
    if fam in ("dense", "vlm") or (fam == "moe" and cfg.mla is None):
        return {
            "k": "layers batch kv_seq kv_heads _",
            "v": "layers batch kv_seq kv_heads _",
            "pos": pos_ax,
        }
    if fam == "moe":
        return {
            "ckv": "layers batch kv_seq _",
            "krope": "layers batch kv_seq _",
            "pos": pos_ax,
        }
    if fam == "hybrid":
        return {
            "ssm_h": "layers batch heads _ _",
            "conv": "layers batch _ mlp",
            "attn_k": "_ batch kv_seq kv_heads _",
            "attn_v": "_ batch kv_seq kv_heads _",
            "pos": pos_ax,
        }
    if fam == "ssm":
        per = []
        for i in range(cfg.n_layers):
            if _xlstm_kind(cfg, i) == "slstm":
                per.append({"c": "batch _", "n": "batch _", "m": "batch _", "h": "batch _"})
            else:
                per.append({"C": "batch heads _ _", "n": "batch heads _", "m": "batch heads"})
        return {"layers": per, "pos": pos_ax}
    if fam == "audio":
        return {
            "k": "layers batch kv_seq kv_heads _",
            "v": "layers batch kv_seq kv_heads _",
            "memory": "batch _ _",
            "pos": pos_ax,
        }
    raise ValueError(fam)


def decode_step(p, cache, tokens, cfg, *, live=None):
    """One decode step: tokens (B, S_new) → (logits (B,S_new,V), new cache).

    ``cache["pos"]`` is per-slot (B,) — every batch slot decodes at its own
    offset (continuous batching; see serving/).  S_new > 1 runs a cached
    chunked prefill (used by the serving engine's prompt buckets); with
    latent attention such a chunk is a prompt from position 0.

    With ``live`` (B,) bool, a MoE model also returns its routing counts
    over the live slots, summed over its MoE layers: int32[2] of
    assignments to the experts held here and held experts hit
    (``moe.apply_moe``); the return is then (logits, cache, counts).
    """
    fam = cfg.family
    pos_raw = jnp.asarray(cache["pos"])
    synced = pos_raw.ndim == 0                   # scalar: synchronized decode
    pos = jnp.broadcast_to(pos_raw, (tokens.shape[0],)).astype(jnp.int32)
    B, S_new = tokens.shape
    x = L.embed_tokens(p["embed"], tokens, cfg)
    # per-slot offsets; multi-token chunks get consecutive positions
    positions = pos[:, None] + jnp.arange(S_new, dtype=jnp.int32)[None, :]
    routing = jnp.zeros((2,), jnp.int32)

    if fam in ("dense", "vlm", "moe") and cfg.mla is not None:
        x, new_cache, routing = _decode_mla(p, cache, x, cfg, positions, pos, live)
    elif fam in ("dense", "vlm", "moe"):
        windows = _window_schedule(cfg)

        def body(x, inp):
            if windows is None:
                lp, (ck, cv) = inp
                w = None
            else:
                lp, (ck, cv), w = inp
            lcache = {"k": ck, "v": cv, "pos": pos}
            h = L.apply_norm(lp["ln1"], x, cfg)
            # deferred append: read-only cache here; the new K/V of all
            # layers are written after the scan (see layers.append_kv)
            attn_out, (new_k, new_v) = L.attention(
                lp["attn"], h, cfg, positions=positions, layer_window=w,
                cache=lcache, update_cache=False,
            )
            if cfg.post_attn_norm:
                attn_out = L.apply_norm(lp["ln_post_attn"], attn_out, cfg)
            x = x + attn_out
            ffn_out, counts = _decode_ffn(lp, x, cfg, live)
            ys = (new_k, new_v) if cfg.moe is None else (new_k, new_v, counts)
            return x + ffn_out, ys

        kv = (cache["k"], cache["v"])
        if windows is None:
            x, ys = jax.lax.scan(body, x, (p["layers"], kv), unroll=cfg.layer_unroll)
        else:
            x, ys = jax.lax.scan(body, x, (p["layers"], kv, windows), unroll=cfg.layer_unroll)
        nk, nv = ys[:2]
        if cfg.moe is not None:
            routing = ys[2].sum(axis=0)
        if synced:
            # one update for all layers and slots, in place when the
            # cache is donated
            ck = jax.lax.dynamic_update_slice(
                cache["k"], nk.astype(cache["k"].dtype), (0, 0, pos_raw, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], nv.astype(cache["v"].dtype), (0, 0, pos_raw, 0, 0))
        else:
            ck, cv = L.append_kv(cache["k"], cache["v"], nk, nv, pos)
        new_cache = {"k": ck, "v": cv, "pos": cache["pos"] + S_new}
    elif fam == "hybrid":
        x, new_cache = _decode_hybrid(p, cache, x, cfg, positions)
    elif fam == "ssm":
        new_layers = []
        for i, lp in enumerate(p["layers"]):
            x, nc = _xlstm_layer(
                lp, x, cfg, kind=_xlstm_kind(cfg, i),
                cache=cache["layers"][i], return_cache=True,
            )
            new_layers.append(nc)
        new_cache = {"layers": new_layers, "pos": pos + 1}
    elif fam == "audio":
        x, new_cache = _decode_encdec(p, cache, x, cfg, positions)
    else:
        raise ValueError(fam)

    x = L.apply_norm(p["final_norm"], x, cfg)
    logits = L.unembed(p["embed"], x, cfg)
    if live is not None and cfg.moe is not None:
        return logits, new_cache, routing
    return logits, new_cache


def _decode_ffn(lp, x, cfg, live):
    """The FFN half of a decode block on the residual ``x``: (its output,
    routing counts int32[2], zero for a dense FFN).  Experts run dropless."""
    h = L.apply_norm(lp["ln2"], x, cfg)
    counts = jnp.zeros((2,), jnp.int32)
    if "moe" in lp:
        with jax.named_scope("moe"):
            ffn_out, aux = apply_moe(lp["moe"], h, cfg, dropless=True, live=live)
        if live is not None:
            counts = aux
        if "ffn" in lp:
            ffn_out = ffn_out + L.apply_ffn(lp["ffn"], h, cfg)
    else:
        ffn_out = L.apply_ffn(lp["ffn"], h, cfg)
    if cfg.post_attn_norm:
        ffn_out = L.apply_norm(lp["ln_post_ffn"], ffn_out, cfg)
    return ffn_out, counts


def _decode_mla(p, cache, x, cfg, positions, pos, live):
    """The layer stack of a latent-attention model over its latent cache:
    the leading dense layers, then the MoE layers, each group one scan that
    reads its layers' cache rows (read only); then every layer's new latent
    rows are written in place, one update per slot (``layers.append_kv``).
    Returns (x, new cache, routing counts)."""
    groups = [(p["dense_layers"], 0)] if "dense_layers" in p else []
    groups.append((p["layers"], cfg.first_dense_layers))
    ckv_all, krope_all = cache["ckv"], cache["krope"]

    def body(x, inp):
        lp, li = inp
        lcache = {"ckv": jax.lax.dynamic_index_in_dim(ckv_all, li, 0, keepdims=False),
                  "krope": jax.lax.dynamic_index_in_dim(krope_all, li, 0, keepdims=False),
                  "pos": pos}
        h = L.apply_norm(lp["ln1"], x, cfg)
        with jax.named_scope("mla"):
            attn_out, rows = mla_attention(lp["attn"], h, cfg, positions=positions, cache=lcache)
        x = x + attn_out
        ffn_out, counts = _decode_ffn(lp, x, cfg, live)
        return x + ffn_out, (rows, counts)

    new_ckv, new_krope, routing = [], [], jnp.zeros((2,), jnp.int32)
    for stack, first in groups:
        n = jax.tree_util.tree_leaves(stack)[0].shape[0]
        layer_ids = jnp.arange(first, first + n, dtype=jnp.int32)
        x, ((c_kv, k_rope), counts) = jax.lax.scan(
            body, x, (stack, layer_ids), unroll=n if cfg.scan_unroll else 1)
        new_ckv.append(c_kv)
        new_krope.append(k_rope)
        routing = routing + counts.sum(axis=0)
    ckv, krope = L.append_kv(ckv_all, krope_all, jnp.concatenate(new_ckv),
                             jnp.concatenate(new_krope), pos)
    S_new = positions.shape[1]
    return x, {"ckv": ckv, "krope": krope, "pos": cache["pos"] + S_new}, routing


def _decode_hybrid(p, cache, x, cfg, positions):
    every = cfg.hybrid_attn_every
    pos = cache["pos"]
    idxs = jnp.arange(cfg.n_layers)

    def body(carry, inp):
        x, ak, av = carry
        lp, (hs, conv), i = inp
        h = L.apply_norm(lp["ln"], x, cfg)
        out, nc = mamba2_block(lp["mamba"], h, cfg, cache={"h": hs, "conv": conv})
        x = x + out

        app = i // every

        def with_attn(operand):
            x, ak, av = operand
            ck = jax.lax.dynamic_index_in_dim(ak, app, 0, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(av, app, 0, keepdims=False)
            h = L.apply_norm(p["shared_attn"]["ln1"], x, cfg)
            attn_out, nkv = L.attention(
                p["shared_attn"]["attn"], h, cfg, positions=positions,
                cache={"k": ck, "v": cv, "pos": pos},
            )
            x = x + attn_out
            h = L.apply_norm(p["shared_attn"]["ln2"], x, cfg)
            x = x + L.apply_ffn(p["shared_attn"]["ffn"], h, cfg)
            ak = jax.lax.dynamic_update_index_in_dim(ak, nkv["k"], app, 0)
            av = jax.lax.dynamic_update_index_in_dim(av, nkv["v"], app, 0)
            return x, ak, av

        x, ak, av = jax.lax.cond(
            (i % every) == (every - 1), with_attn, lambda o: o, (x, ak, av)
        )
        return (x, ak, av), (nc["h"], nc["conv"])

    (x, ak, av), (hs, conv) = jax.lax.scan(
        body, (x, cache["attn_k"], cache["attn_v"]),
        (p["layers"], (cache["ssm_h"], cache["conv"]), idxs),
    )
    new_cache = {
        "ssm_h": hs, "conv": conv, "attn_k": ak, "attn_v": av, "pos": pos + 1
    }
    return x, new_cache


def _decode_encdec(p, cache, x, cfg, positions):
    pos = cache["pos"]
    memory = cache["memory"]

    def body(x, inp):
        lp, (ck, cv) = inp
        h = L.apply_norm(lp["ln1"], x, cfg)
        o, nc = L.attention(
            lp["self_attn"], h, cfg, positions=positions,
            cache={"k": ck, "v": cv, "pos": pos},
        )
        x = x + o
        h = L.apply_norm(lp["ln2"], x, cfg)
        x = x + L.cross_attention(lp["cross_attn"], h, memory, cfg)
        h = L.apply_norm(lp["ln3"], x, cfg)
        x = x + L.apply_ffn(lp["ffn"], h, cfg)
        return x, (nc["k"], nc["v"])

    x, (nk, nv) = jax.lax.scan(body, x, (p["decoder"], (cache["k"], cache["v"])), unroll=cfg.layer_unroll)
    new_cache = {"k": nk, "v": nv, "memory": memory, "pos": pos + 1}
    return x, new_cache


def encode_memory(p, frames, cfg):
    """Run the encoder once (enc-dec prefill) and return memory."""
    enc_x = frames.astype(jnp.dtype(cfg.dtype)) @ p["frontend_proj"]["w"]
    B, T = enc_x.shape[:2]
    enc_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def enc_body(x, lp):
        h = L.apply_norm(lp["ln1"], x, cfg)
        o, _ = L.attention(lp["attn"], h, cfg, positions=enc_pos, causal=False)
        x = x + o
        h = L.apply_norm(lp["ln2"], x, cfg)
        x = x + L.apply_ffn(lp["ffn"], h, cfg)
        return x, None

    enc_x, _ = jax.lax.scan(enc_body, enc_x, p["encoder"], unroll=cfg.enc_unroll)
    return L.apply_norm(p["enc_final_norm"], enc_x, cfg)
