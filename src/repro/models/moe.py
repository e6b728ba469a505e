"""Mixture-of-Experts FFN: routing over every expert, computed for the
experts held here.

Supports the two assigned MoE geometries:
* Arctic  — 128 routed experts top-2 **plus a parallel dense FFN branch**
  (dense-MoE hybrid: output = dense(x) + moe(x));
* DeepSeek-V2 — 2 *shared* experts (always on) + 160 routed experts top-6,
  routed by ``group_limited_greedy`` (see :func:`route`).

Routing scores all ``num_experts``.  Under expert parallelism a chip holds
``MoEConfig.held`` = (first, count) of them and computes only their part of
the result (plus the shared experts, which every chip holds); what the
other experts add is another chip's part.

Two dispatches:
* **dropless** (:func:`apply_moe` with ``dropless=True``; the serving path
  of ``decode_step``, both prefill and decode): each held expert runs over
  every token (capacity = N) and the gates weight its output, so a token's
  output never depends on its batch neighbours;
* **capacity** (training ``forward``): tokens go to their top-k experts
  with a fixed per-expert capacity C = ceil(N·k/E · capacity_factor),
  overflow dropped (the residual path keeps the token alive); expert
  compute is a *grouped* matmul with the expert dim on the ``expert``
  logical axis, expert-parallel over the ``model`` mesh axis.  It needs
  every expert held.

The router's aux load-balancing loss (Shazeer-style) is returned alongside.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain, gather_fsdp

from .layers import _act, dense_init


def init_moe(key, cfg):
    m = cfg.moe
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    held = m.held[1]
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, m.num_experts), jnp.float32),
        "w_gate": dense_init(ks[1], (held, d, m.d_ff_expert), dt),
        "w_up": dense_init(ks[2], (held, d, m.d_ff_expert), dt),
        "w_down": dense_init(ks[3], (held, m.d_ff_expert, d), dt, in_axis=1),
    }
    a = {
        "router": "fsdp _",
        "w_gate": "expert fsdp mlp",
        "w_up": "expert fsdp mlp",
        "w_down": "expert mlp fsdp",
    }
    if m.num_shared_experts:
        f_sh = m.d_ff_shared * m.num_shared_experts
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(kss[0], (d, f_sh), dt),
            "w_up": dense_init(kss[1], (d, f_sh), dt),
            "w_down": dense_init(kss[2], (f_sh, d), dt),
        }
        a["shared"] = {"w_gate": "fsdp mlp", "w_up": "fsdp mlp", "w_down": "mlp fsdp"}
    return p, a


def route(probs: jax.Array, m) -> tuple[jax.Array, jax.Array]:
    """Gates and expert ids (N, top_k) from router probabilities (N, E).

    With ``n_group`` > 1, DeepSeek-V2's ``group_limited_greedy``: a group's
    score is the highest probability among its E / n_group experts, the
    ``topk_group`` best groups are kept, and top-k runs over the kept
    experts only.  Ties go to the lower index (``lax.top_k``).  Gates are
    renormalised when ``norm_topk_prob``, else scaled by
    ``routed_scaling_factor``."""
    N, E = probs.shape
    scores = probs
    if m.n_group > 1:
        group_best = probs.reshape(N, m.n_group, E // m.n_group).max(axis=-1)
        _, keep = jax.lax.top_k(group_best, m.topk_group)            # (N, topk_group)
        kept = jnp.zeros((N, m.n_group), bool).at[jnp.arange(N)[:, None], keep].set(True)
        scores = jnp.where(jnp.repeat(kept, E // m.n_group, axis=1), probs, 0.0)
    gate_vals, expert_ids = jax.lax.top_k(scores, m.top_k)         # (N, K)
    if m.norm_topk_prob:
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)
    else:
        gate_vals = gate_vals * m.routed_scaling_factor
    return gate_vals, expert_ids


def apply_moe(
    p, x: jax.Array, cfg, *, dropless: bool = False,
    live: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) → (out, aux).

    ``aux`` is the load-balancing loss, or with ``live`` (B,) bool, the
    routing counts int32[2] over the live rows' tokens: assignments to the
    experts held here, and held experts that took at least one token."""
    m = cfg.moe
    B, S, D = x.shape
    N = B * S
    E, K = m.num_experts, m.top_k
    xf = x.reshape(N, D)

    # ---- router --------------------------------------------------------
    logits = (xf.astype(jnp.float32) @ p["router"])            # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = route(probs, m)                    # (N, K)

    if dropless:
        out = _dropless(p, xf, gate_vals, expert_ids, cfg)
    else:
        out = _capacity(p, xf, gate_vals, expert_ids, cfg)

    # ---- shared experts (DeepSeek) ---------------------------------------
    if "shared" in p:
        sh = p["shared"]
        sg = gather_fsdp(sh["w_gate"], "fsdp", "mlp", group="moe")
        su = gather_fsdp(sh["w_up"], "fsdp", "mlp", group="moe")
        sd = gather_fsdp(sh["w_down"], "mlp", "fsdp", group="moe")
        hs = _act(xf @ sg, cfg.activation) * (xf @ su)
        out = out + hs @ sd

    if live is not None:
        first, count = m.held
        here = (expert_ids >= first) & (expert_ids < first + count)
        here &= jnp.repeat(live, S)[:, None]
        hits = jnp.zeros((E,), jnp.int32).at[expert_ids].add(here.astype(jnp.int32))
        aux = jnp.stack([here.sum(dtype=jnp.int32),
                         (hits[first:first + count] > 0).sum(dtype=jnp.int32)])
    else:
        # aux load-balance loss (mean prob × token fraction per expert)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0) / (N * K)
        aux = m.router_aux_loss * E * jnp.sum(me * ce)
    return out.reshape(B, S, D), aux


def _dropless(p, xf, gate_vals, expert_ids, cfg) -> jax.Array:
    """Every held expert over every token, weighted by its gate (0 where
    the token did not pick it)."""
    first, count = cfg.moe.held
    N = xf.shape[0]
    gates = jnp.zeros((N, cfg.moe.num_experts), jnp.float32)
    gates = gates.at[jnp.arange(N)[:, None], expert_ids].set(gate_vals)
    gates = gates[:, first:first + count]                      # (N, held)
    w_gate = gather_fsdp(p["w_gate"], "expert", "fsdp", "mlp", group="moe")
    w_up = gather_fsdp(p["w_up"], "expert", "fsdp", "mlp", group="moe")
    w_down = gather_fsdp(p["w_down"], "expert", "mlp", "fsdp", group="moe")
    g = _act(jnp.einsum("nd,edf->enf", xf, w_gate), cfg.activation)
    u = jnp.einsum("nd,edf->enf", xf, w_up)
    eo = jnp.einsum("enf,efd->end", g * u, w_down)             # (held, N, D)
    return jnp.einsum("end,ne->nd", eo, gates.astype(xf.dtype))


def _capacity(p, xf, gate_vals, expert_ids, cfg) -> jax.Array:
    """Sort-based capacity dispatch over all (held) experts."""
    m = cfg.moe
    N, D = xf.shape
    E, K = m.num_experts, m.top_k
    if m.held != (0, E):
        raise NotImplementedError(
            "capacity dispatch needs every expert held; a share of the "
            "experts runs dropless")
    # Small batches (decode steps, smoke tests) run dropless so that
    # step-by-step decode agrees with the full-sequence forward; at scale the
    # paper-standard capacity factor bounds the grouped-matmul shape.
    if N <= 64:
        cap = N
    else:
        cap = int(max(K, round(N * K / E * m.capacity_factor)))
    flat_e = expert_ids.reshape(-1)                            # (N*K,)
    flat_g = gate_vals.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(N), K)

    # Sort tokens by expert and derive each token's slot from its rank within
    # the expert's run: the classic one-hot cumsum's order (a stable sort keeps
    # token order per expert) without its (N·K, E) prefix sum.
    order = jnp.argsort(flat_e, stable=True)                   # (N*K,)
    sorted_e = flat_e[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts                       # (E,)
    pos_in_run = jnp.arange(flat_e.shape[0], dtype=jnp.int32) - starts[sorted_e]
    slot = jnp.zeros_like(flat_e).at[order].set(pos_in_run)    # (N*K,) 0-based
    keep = slot < cap
    slot = jnp.where(keep, slot, cap)                          # overflow -> pad row

    # scatter token features into (E, cap+1, D); row `cap` is the trash slot.
    # (A 2D expert x capacity sharding makes GSPMD replicate the buffers: it
    # cannot plan the data-dependent scatter as an all-to-all.)
    buf = jnp.zeros((E, cap + 1, D), xf.dtype)
    buf = buf.at[flat_e, slot].add(jnp.where(keep[:, None], xf[flat_tok], 0))
    buf = constrain(buf, "expert", "_", "_")
    h = buf[:, :cap]                                           # (E, cap, D)

    # ---- grouped expert FFN (the packed "parallel branches") -------------
    w_gate = gather_fsdp(p["w_gate"], "expert", "fsdp", "mlp", group="moe")
    w_up = gather_fsdp(p["w_up"], "expert", "fsdp", "mlp", group="moe")
    w_down = gather_fsdp(p["w_down"], "expert", "mlp", "fsdp", group="moe")
    g = _act(jnp.einsum("ecd,edf->ecf", h, w_gate), cfg.activation)
    u = jnp.einsum("ecd,edf->ecf", h, w_up)
    eo = jnp.einsum("ecf,efd->ecd", g * u, w_down)             # (E, cap, D)
    eo = constrain(eo, "expert", "_", "_")

    # ---- combine back ----------------------------------------------------
    gathered = eo[flat_e, jnp.minimum(slot, cap - 1)]          # (N*K, D)
    weight = jnp.where(keep, flat_g, 0.0).astype(xf.dtype)
    return jnp.zeros((N, D), xf.dtype).at[flat_tok].add(gathered * weight[:, None])
