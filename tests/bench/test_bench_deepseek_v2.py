"""The DeepSeek-V2 configuration (``bench/arch/deepseek_v2.py``) against the
program at a small DeepSeek-shaped size on the CPU: the engine's prefill
and latent-cache decode against the reference's full forward, a chip's
share of the experts, dropless routing, the routing rule; the committed
configuration's counts; its mix; and the two readers of the program's
``mla`` / ``moe`` scopes and routing counters on a synthetic trace."""

from __future__ import annotations

import dataclasses
import json
import math
from types import SimpleNamespace

import _bench_smoke as S
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import generator, run, serve, spec, trace, weights, work, xspace
from bench.arch import deepseek_v2 as A
from bench.peaks import PEAKS
from repro.models import decode_step, init_cache
from repro.models import moe as moe_mod
from repro.obs.tracer import SpanTracer
from repro.serving import Request, ServingEngine

CONFIG = S.REPO / "bench/configs/deepseek-v2-ep20.json"
SEED = 2**40 + 11


def small(dtype: str = "float32", held=(0, 8), experts: int = 16) -> dict:
    """The committed model section at a small size: 1 dense + 2 MoE layers,
    16 experts in 4 groups (top-2 groups, top-3 experts), 8 of them held."""
    model = json.loads(CONFIG.read_text())["model"]
    model.update(name="dsv2-small", n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
                 vocab=512, first_dense_d_ff=128, dtype=dtype)
    model["mla"] = dict(kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
                        qk_rope_head_dim=8, v_head_dim=16)
    model["moe"] = dict(model["moe"], num_experts=experts, n_group=4, topk_group=2, top_k=3,
                        d_ff_expert=32, d_ff_shared=32, experts_held=list(held))
    return model


# --- the program against the reference --------------------------------------


def test_prefill_then_latent_decode_matches_the_reference_forward():
    model = small()
    params = weights.make(A.layout(model), SEED)
    cfg = serve.model_config(model)
    toks = np.random.default_rng(0).integers(0, model["vocab"], (1, 40)).astype(np.int32)
    prompt = 24
    cache = init_cache(cfg, 1, 64)
    logits, cache = decode_step(params, cache, jnp.asarray(toks[:, :prompt]), cfg)
    got = [np.asarray(logits[0])]
    for t in range(prompt, toks.shape[1]):                 # absorbed, one token a step
        logits, cache = decode_step(params, cache, jnp.asarray(toks[:, t:t + 1]), cfg)
        got.append(np.asarray(logits[0]))
    got = np.concatenate(got)[:, :model["vocab"]]
    n = toks.shape[1]
    ref = np.asarray(A.logits_at(params, model, toks, np.zeros(n, int), np.arange(n)))
    # both in float32: they differ only in the order of sums (the absorbed
    # form folds W_uk into the query), 2e-5 at this size on logits of
    # magnitude 4; a missing term (a latent norm, YaRN's scale, an expert)
    # moves them by 0.1 or more
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-4)
    fp8 = np.asarray(A.logits_at(params, model, toks, np.zeros(n, int), np.arange(n), mode="fp8"))
    assert np.abs(fp8 - ref).max() > 0.1


def test_engine_serves_the_reference_greedy_tokens():
    # the sealed prefill and decode through ServingEngine on two requests
    # sharing the batch, in float32: every served token is the float32
    # reference's best (the two differ in the order of sums alone).  At
    # this toy width bfloat16 is no test: a rounding flips a routed expert
    # that carries a third of the layer's output (8 of 16 held, top-3).
    model = small()
    params = weights.make(A.layout(model), SEED)
    eng = ServingEngine(serve.model_config(model), params, max_slots=4, max_len=96,
                        prompt_buckets=(16, 32))
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, 512, n).astype(np.int32), max_new_tokens=24)
            for i, n in enumerate((13, 30))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    picks = [SimpleNamespace(req=r, lane="replica0") for r in reqs]
    gap = _gap(params, model, picks)
    assert gap["tokens"] == 48
    assert gap["gap_max"] <= 1e-3


def _gap(params, model, picks, control=False):
    from bench import check

    return check.widest_gap(A, params, model, picks, 96, control)


# --- a chip's share of the experts ------------------------------------------


def _moe_params(model, seed, held):
    """One MoE layer's program parameters (float32) for the share ``held``,
    cut from the weights of the whole layer."""
    whole = weights.make(A.layout(small(held=(0, model["moe"]["num_experts"]))), seed)
    lp = jax.tree.map(lambda t: t[0], whole["layers"]["moe"])
    first, count = held
    return {**lp, **{k: lp[k][first:first + count] for k in ("w_gate", "w_up", "w_down")}}


def test_the_shares_of_all_experts_add_up_to_the_whole_layer():
    model = small()
    E = model["moe"]["num_experts"]
    x = jax.random.normal(jax.random.key(3), (2, 12, model["d_model"]), jnp.float32)
    parts = []
    for first in range(0, E, 4):
        cfg = serve.model_config(dict(model, moe=dict(model["moe"], experts_held=[first, 4])))
        out, _ = moe_mod.apply_moe(_moe_params(model, SEED, (first, 4)), x, cfg, dropless=True)
        parts.append(np.asarray(out))
    # every share adds the shared experts: count them once
    shared = np.asarray(A._swiglu(x.reshape(-1, model["d_model"]),
                                  _moe_params(model, SEED, (0, 0))["shared"], "float32"))
    total = sum(parts) - (len(parts) - 1) * shared.reshape(x.shape)
    whole = dict(model["moe"], experts_held=[0, E])
    lp = _moe_params(model, SEED, (0, E))
    with jax.default_matmul_precision("highest"):
        ref = A._moe(x.reshape(-1, model["d_model"]), lp, whole, "float32")
    np.testing.assert_allclose(total.reshape(-1, model["d_model"]), np.asarray(ref),
                               rtol=0, atol=2e-5)


def test_a_tokens_output_does_not_depend_on_its_neighbours():
    model = small(held=(0, 16))
    cfg = serve.model_config(model)
    lp = _moe_params(model, SEED, (0, 16))
    crowd = jax.random.normal(jax.random.key(4), (8, 16, model["d_model"]), jnp.float32)
    alone, _ = moe_mod.apply_moe(lp, crowd[7:], cfg, dropless=True)
    among, _ = moe_mod.apply_moe(lp, crowd, cfg, dropless=True)       # 112 neighbours
    # equal up to float32 rounding of matmuls of another shape (2.7e-7 of
    # outputs up to 13 here); a dropped expert moves them by O(1)
    np.testing.assert_allclose(np.asarray(among[7:]), np.asarray(alone), rtol=1e-6, atol=1e-5)
    # the training path's capacity dispatch drops the late tokens here
    dropped, _ = moe_mod.apply_moe(lp, crowd, cfg)
    assert np.abs(np.asarray(dropped[7:]) - np.asarray(alone)).max() > 1e-3


def _numpy_rule(probs, n_group, topk_group, top_k, scale):
    """The published group_limited_greedy, ties to the lower index."""
    N, E = probs.shape
    gates, ids = np.zeros((N, top_k)), np.zeros((N, top_k), int)
    for n in range(N):
        best = probs[n].reshape(n_group, -1).max(1)
        groups = np.argsort(-best, kind="stable")[:topk_group]
        mask = np.zeros(E, bool)
        for g in groups:
            mask[g * (E // n_group):(g + 1) * (E // n_group)] = True
        scores = np.where(mask, probs[n], 0.0)
        ids[n] = np.argsort(-scores, kind="stable")[:top_k]
        gates[n] = scores[ids[n]] * scale
    return gates, ids


def test_group_limited_greedy_follows_the_published_rule():
    m = serve.model_config(small()).moe
    rng = np.random.default_rng(7)
    probs = rng.dirichlet(np.ones(16), 64).astype(np.float32)
    # group ties: groups 1 and 3 share their best score; groups 0 and 2
    # tie on everything
    probs[0] = np.full(16, 1 / 16, np.float32)
    probs[1, [5, 13]] = probs[1].max() + 0.1
    probs[1] /= probs[1].sum()
    want_g, want_i = _numpy_rule(probs, m.n_group, m.topk_group, m.top_k, m.routed_scaling_factor)
    for rule in (lambda p: moe_mod.route(p, m), lambda p: A._route(p, dataclasses.asdict(m))):
        gates, ids = rule(jnp.asarray(probs))
        np.testing.assert_array_equal(np.asarray(ids), want_i)
        np.testing.assert_allclose(np.asarray(gates), want_g, rtol=1e-6)


# --- the committed configuration ---------------------------------------------


def test_the_committed_file_holds_the_published_config():
    conf = json.loads(CONFIG.read_text())
    model, cfg = conf["model"], serve.model_config(conf["model"])
    assert set(conf["reduced"]) == {"num_hidden_layers", "n_routed_experts"}
    assert conf["num_hidden_layers"] == model["n_layers"] == 9
    assert conf["n_routed_experts"] == cfg.moe.held[1] == 8
    assert conf["published"] == {"num_hidden_layers": 60, "n_routed_experts": 160}
    assert cfg.moe.num_experts == 160 and cfg.moe.top_k == conf["num_experts_per_tok"]
    assert (cfg.moe.n_group, cfg.moe.topk_group) == (conf["n_group"], conf["topk_group"])
    assert cfg.moe.routed_scaling_factor == conf["routed_scaling_factor"]
    assert cfg.moe.norm_topk_prob is conf["norm_topk_prob"] is False
    assert cfg.d_model == conf["hidden_size"] and cfg.n_heads == conf["num_attention_heads"]
    assert cfg.first_dense_layers == conf["first_k_dense_replace"]
    assert cfg.first_dense_d_ff == conf["intermediate_size"]
    assert cfg.moe.d_ff_expert == conf["moe_intermediate_size"]
    assert cfg.moe.num_shared_experts == conf["n_shared_experts"]
    assert cfg.vocab == conf["vocab_size"] and cfg.norm_eps == conf["rms_norm_eps"]
    for k in ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"):
        assert getattr(cfg.mla, k) == conf[k]
    rs = conf["rope_scaling"]
    assert dataclasses.asdict(cfg.rope_scaling) == {
        k: rs[k] for k in ("factor", "original_max_position_embeddings", "beta_fast",
                           "beta_slow", "mscale", "mscale_all_dim")}
    assert spec.load_cell("deepseekv2-longgen").arch.__name__.startswith("bench_arch_deepseek_v2")


def test_the_committed_mix_fits_the_engine():
    cell = spec.load_cell("deepseekv2-longgen")
    assert serve.used_buckets(cell) == (128, 256, 512, 1024)
    generator.check_fits(cell.traffic, cell.engine["max_len"], serve.used_buckets(cell))
    assert cell.traffic["prompt"]["max"] + cell.traffic["output"]["max"] <= 4095
    assert [m["name"] for m in cell.end_to_end] == ["tpot_ms", "tpot_p95_ms", "setup_s"]


# --- the committed configuration's counts, by hand --------------------------

DSV2 = json.loads(CONFIG.read_text())["model"]
# one layer's MLA (q down, q up, kv down with the rope key, k / v up, out),
# the dense FFN, one expert, the 2 shared experts, the router, the head
MLA = 5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 2 * 512 * 128 * 128 + 128 * 128 * 5120
DENSE_FFN, EXPERT, SHARED = 3 * 5120 * 12288, 3 * 5120 * 1536, 2 * 3 * 5120 * 1536
ROUTER, HEAD = 5120 * 160, 5120 * 102400
NORMS = 9 * (2 * 5120 + 512 + 1536) + 5120           # two a layer, the latents', the final
# every weight once: bf16, the router float32, the norms float32
WEIGHT_BYTES = (2 * (HEAD + 9 * MLA + DENSE_FFN + 8 * (8 * EXPERT + SHARED))
                + 4 * 8 * ROUTER + 4 * NORMS)
ROW = 9 * 576 * 2                                    # a cached position, all layers
PER_KEY = 2 * 128 * (512 + 64 + 512)                 # absorbed scores and context, a key


def test_deepseek_v2_layout_holds_its_4_47b_parameters():
    shapes = A.Shapes.from_model(DSV2)
    n = sum(math.prod(s) for s, *_ in A.layout(DSV2).values())
    # 9 layers of MLA 149.2M, the dense FFN 188.7M, 8 x (8 held + 2 shared
    # experts x 23.6M + router 0.8M), embedding and head 2 x 524.3M, norms
    assert n == shapes.param_count() == 4_474_455_040
    assert shapes.mla_params == MLA == 149_225_472
    # the program's own count leaves the norms out
    assert serve.model_config(DSV2).param_count + NORMS == n


@pytest.mark.parametrize("count", ["decode", "prefill", "mla_decode", "moe_decode"])
def test_deepseek_v2_work_counts_by_hand(count):
    shapes = A.Shapes.from_model(DSV2)
    pos, p = [100, 2047], 700
    routed = 6 * 8 / 160 * 8 * EXPERT                # a token's held-expert work, 8 layers
    outside = 9 * MLA + DENSE_FFN + 8 * (SHARED + ROUTER)
    want = {
        "decode": (int(2 * 2 * (outside + routed) + 2 * 2 * HEAD + 9 * PER_KEY * (101 + 2048)),
                   WEIGHT_BYTES + 2 * 2 * 5120 + sum(pos) * ROW + 2 * ROW),
        "prefill": (int(2 * p * (outside + routed) + 2 * HEAD
                        + 9 * 2 * 128 * (128 + 64 + 128) * p * (p + 1) // 2),
                    WEIGHT_BYTES + p * 2 * 5120 + p * ROW),
        "mla_decode": (9 * (2 * 2 * MLA + PER_KEY * 2149), 9 * MLA * 2 + sum(pos) * ROW + 2 * ROW),
        "moe_decode": (2 * 64 * 8 * (SHARED + ROUTER) + 2 * 30 * EXPERT,
                       12 * EXPERT * 2 + 8 * (SHARED * 2 + ROUTER * 4)),
    }[count]
    got = {"decode": lambda: shapes.decode(pos), "prefill": lambda: shapes.prefill(p),
           "mla_decode": lambda: shapes.mla_decode(pos),
           "moe_decode": lambda: shapes.moe_decode(30, 12, 64)}[count]()
    assert got == want


# --- the engine's routing counters -------------------------------------------


@pytest.mark.parametrize("enabled", [True, False])
def test_the_engine_counts_routing_only_while_tracing(enabled):
    model = small("bfloat16")
    tracer = SpanTracer()
    if enabled:
        tracer.enable()
    eng = ServingEngine(serve.model_config(model), weights.make(A.layout(model), SEED),
                        max_slots=4, max_len=64, prompt_buckets=(16,), tracer=tracer)
    rng = np.random.default_rng(2)
    for i, n in enumerate((8, 5, 5)):
        eng.submit(Request(rid=i, prompt=rng.integers(0, 512, 9).astype(np.int32),
                           max_new_tokens=n))
    eng.run_until_drained()
    counters = [e for e in tracer.drain() if e.ph == "C" and e.cat == "moe"]
    tracer.disable()
    if not enabled:
        assert counters == []
        return
    here = [e.args["value"] for e in counters if e.name == "moe.tokens_here"]
    hit = [e.args["value"] for e in counters if e.name == "moe.experts_hit"]
    assert len(here) == len(hit) == eng.stats.steps == 7
    # up to three live slots a step, 2 MoE layers, top-3 of 16 with 8 held;
    # the last three steps have one live slot
    assert all(0 <= h <= 3 * 2 * 3 for h in here) and all(0 <= h <= 2 * 8 for h in hit)
    assert all(h <= 1 * 2 * 3 for h in here[-3:]) and all(a >= b for a, b in zip(here, hit))
    assert here[0] > 0


# --- the scope readers on a synthetic trace ----------------------------------

DECODE = "jit_decode_body(3)"
LOOP = "jit(decode_body)/while"
MLA_OP, MOE_OP = f"{LOOP}/body/closed_call/mla/dot_general", f"{LOOP}/body/closed_call/moe/add"
# Window 0..1000 us; step 0 (0..420) holds a decode run 100..400, step 1
# (500..900) one 550..850.  Run 0: the layer loop 100..400 (not scoped),
# MLA ops 100..180 and 150..200 (overlapping: 100 us), MoE 200..260, other
# 260..300.  Run 1: MLA 550..650, MoE 650..700 (its scope interned as a
# reference), a dense add 700..720.
DEVICE = {
    "XLA Modules": [(DECODE, 100, 300, {}), (DECODE, 550, 300, {})],
    "XLA Ops": [("%while.3 = (s32[]) while(%t)", 100, 300, {"tf_op": LOOP}),
                ("%fusion.1 = bf16[64] fusion(%p)", 100, 80, {"tf_op": MLA_OP}),
                ("%fusion.2 = bf16[64] fusion(%p)", 150, 50, {"tf_op": MLA_OP}),
                ("%fusion.3 = bf16[64] fusion(%p)", 200, 60, {"tf_op": MOE_OP}),
                ("%add.4 = bf16[64] add(%p, %q)", 260, 40, {"tf_op": f"{LOOP}/body/add"}),
                ("%fusion.1 = bf16[64] fusion(%p)", 550, 100, {"tf_op": MLA_OP}),
                ("%fusion.5 = bf16[64] fusion(%p)", 650, 50, {"tf_op": ("ref", MOE_OP)}),
                ("%add.4 = bf16[64] add(%p, %q)", 700, 20, {"tf_op": f"{LOOP}/body/add"})],
}
HOST = {"python": [("bench.window", 0, 1000, {}),
                   ("bench.step", 0, 420, {"lane": "replica0", "step": 0}),
                   ("bench.step", 500, 400, {"lane": "replica0", "step": 1})]}


def _write_xspace(path, planes: dict, meta_stats: bool) -> None:
    """``{plane: {line: [(name, start_us, dur_us, {stat: value})]}}`` as a
    serialized XSpace; device operations keep their statistics on their
    metadata, as the TPU profiler does (a ``("ref", text)`` value is
    interned through the plane's stat metadata), host events on the event."""
    space = xspace.messages()["XSpace"]()
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        plane = space.planes.add(id=pid, name=pname)
        names, stats = {}, {}

        def stat_id(key):
            if key not in stats:
                stats[key] = len(stats) + 1
                e = plane.stat_metadata.add(key=stats[key])
                e.value.id, e.value.name = stats[key], key
            return stats[key]

        def fill(target, st):
            for k, v in st.items():
                s = target.stats.add(metadata_id=stat_id(k))
                if isinstance(v, tuple):
                    s.ref_value = stat_id(v[1])
                elif isinstance(v, str):
                    s.str_value = v
                else:
                    s.int64_value = v

        for lid, (lname, events) in enumerate(lines.items(), 1):
            line = plane.lines.add(id=lid, name=lname, timestamp_ns=0)
            for name, start, dur, st in events:
                key = (name, json.dumps(st, sort_keys=True)) if meta_stats else name
                if key not in names:
                    names[key] = len(names) + 1
                    e = plane.event_metadata.add(key=names[key])
                    e.value.id, e.value.name = names[key], name
                    if meta_stats and "device" in pname:
                        fill(e.value, st)
                ev = line.events.add(metadata_id=names[key], offset_ps=int(start * 1e6),
                                     duration_ps=int(dur * 1e6))
                if "host" in pname:
                    fill(ev, st)
    path.write_bytes(space.SerializeToString())


def _ctx(tmp_path, positions, counters):
    path = tmp_path / "t.xplane.pb"
    _write_xspace(path, {"/device:TPU:0": DEVICE, "/host:CPU": HOST}, meta_stats=True)
    tr = trace.reduce_profile(ProfileData.from_file(str(path)))
    recs = [SimpleNamespace(lane="replica0", index=i, prefills=[], positions=p,
                            t0=10.0 + i, t1=10.5 + i) for i, p in enumerate(positions)]
    spans = [SimpleNamespace(ph="C", name=n, ts=10.2 + i, args={"value": v})
             for i, c in enumerate(counters) for n, v in c.items()]
    shapes = A.Shapes.from_model(json.loads(CONFIG.read_text())["model"])
    ctx = run.Context(None, shapes, PEAKS["TPU v5 lite"], None, [], {"replica0": recs},
                      {"replica0": 0}, spans, tr)
    ctx.xplane = path
    return ctx, shapes


def test_the_scoped_readers_on_a_trace_with_scopes_and_counters(tmp_path):
    positions = [[1000, 2000], [1001, 2001, 30]]
    counters = [{"moe.tokens_here": 5, "moe.experts_hit": 4},
                {"moe.tokens_here": 9, "moe.experts_hit": 7}]
    ctx, shapes = _ctx(tmp_path, positions, counters)
    peaks = ctx.peaks
    read = lambda name: spec.reader(name)(ctx)
    need = sum(work.roofline_seconds(*shapes.mla_decode(p), peaks) for p in positions)
    assert read("mla_roofline") == pytest.approx(100 * need / 200e-6)
    need = (work.roofline_seconds(*shapes.moe_decode(5, 4, 2), peaks)
            + work.roofline_seconds(*shapes.moe_decode(9, 7, 3), peaks))
    assert read("moe_roofline") == pytest.approx(100 * need / 110e-6)
    # the whole-step readers read the same runs through the new counts
    assert read("decode_step_ms.dsv2") == pytest.approx(0.3)


def test_the_scoped_readers_read_nothing_without_scopes_or_counters(tmp_path):
    ctx, _ = _ctx(tmp_path, [[10, 20], [11, 21]], [{}, {}])
    assert spec.reader("moe_roofline")(ctx) is None           # no counters
    assert spec.reader("mla_roofline")(ctx) is not None
    plain = tmp_path / "plain.xplane.pb"                       # ops without a scope
    _write_xspace(plain, {"/device:TPU:0": DEVICE, "/host:CPU": HOST}, meta_stats=False)
    ctx2, _ = _ctx(tmp_path, [[10, 20], [11, 21]], [{}, {}])
    ctx2.xplane, ctx2._scoped_ops = plain, None
    assert spec.reader("mla_roofline")(ctx2) is None
    # a dense configuration has no such counts
    from bench.arch import dense

    ctx2.shapes, ctx2._scoped_ops = dense.Shapes.from_model(S.smoke_model()), None
    ctx2.xplane = ctx.xplane
    assert spec.reader("mla_roofline")(ctx2) is None and spec.reader("moe_roofline")(ctx2) is None
