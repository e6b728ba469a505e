"""Serving-engine regressions: drain accounting, schedule-cache wiring, and
dispatcher-vs-direct numerics on a real (smoke) model."""

import dataclasses

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.dispatch import Dispatcher, ScheduleCache
from repro.models import forward, init_model
from repro.serving import Request, ServingEngine


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(C.get("phi4-mini-3.8b", smoke=True), dtype="float32")
    params, _ = init_model(jax.random.key(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def shared_cache():
    return ScheduleCache(capacity=16)


def _engine(model, cache, **kw):
    cfg, params = model
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("prompt_buckets", (8, 16))
    return ServingEngine(cfg, params, schedule_cache=cache, **kw)


def _reqs(cfg, n, max_new=4, seed=1, plen=5):
    rng = np.random.default_rng(seed)
    return [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
                max_new_tokens=max_new)
        for i in range(n)
    ]


def test_one_token_request_not_dropped(model, shared_cache):
    """Regression: a request admitted and finished within the same step()
    used to vanish from run_until_drained's return value."""
    cfg, _ = model
    eng = _engine(model, shared_cache)
    eng.submit(_reqs(cfg, 1, max_new=1)[0])
    done = eng.run_until_drained()
    assert len(done) == 1
    assert done[0].done
    assert len(done[0].generated) == 1     # exactly one token, from prefill
    assert done[0].t_done >= done[0].t_first > 0
    assert eng.idle


def test_mixed_lengths_all_reported_once(model, shared_cache):
    cfg, _ = model
    eng = _engine(model, shared_cache)
    reqs = [r for i, r in enumerate(_reqs(cfg, 6))]
    for i, r in enumerate(reqs):
        r.max_new_tokens = 1 if i % 2 == 0 else 3
        eng.submit(r)
    done = eng.run_until_drained()
    assert sorted(r.rid for r in done) == list(range(6))
    for r in done:
        assert len(r.generated) == r.max_new_tokens


def test_step_returns_finished(model, shared_cache):
    cfg, _ = model
    eng = _engine(model, shared_cache)
    eng.submit(_reqs(cfg, 1, max_new=1)[0])
    finished = eng.step()
    assert [r.rid for r in finished] == [0]


def test_engines_share_sealed_executables(model):
    """The tentpole property: a second engine over the same (cfg, shapes)
    pays zero compiles — the pre-run amortizes through the cache."""
    cache = ScheduleCache(capacity=16)
    first = _engine(model, cache)          # pays the pre-runs
    builds_after_first = cache.stats.builds
    assert builds_after_first > 0
    assert first.stats.prefill_compiles + first.stats.decode_compiles \
        == builds_after_first
    second = _engine(model, cache)
    assert cache.stats.builds == builds_after_first
    assert second.stats.prefill_compiles == 0
    assert second.stats.decode_compiles == 0


def test_bucketing_policy_replaces_prompt_buckets(model, shared_cache):
    cfg, _ = model
    eng = _engine(model, shared_cache, bucketing="pow2:8:16")
    assert eng.prompt_buckets == (8, 16)
    assert eng._bucket(5) == 8
    with pytest.raises(ValueError):
        eng._bucket(17)                    # 32 > pow2 max_bucket 16


def test_engine_validates_unservable_prompt_at_submit(model, shared_cache):
    """Dispatcher submit rejects a prompt beyond the engine's bucket family
    synchronously (the async stepping thread must never see it)."""
    cfg, _ = model
    disp = Dispatcher(max_pending=16)
    disp.register_model("m", _engine(model, shared_cache))   # buckets (8, 16)
    with pytest.raises(ValueError):
        disp.submit("m", np.zeros(17, np.int32))
    assert disp.pending() == 0


def test_prefill_key_memo_is_lru_bounded(model, shared_cache):
    """The per-engine bucket->ScheduleKey memo is bounded, and it memoizes
    only keys — executables remain governed by the shared cache's LRU."""
    eng = _engine(model, shared_cache, warmup=False)
    eng._prefill_key_cap = 1
    eng._get_prefill_exec(8)
    eng._get_prefill_exec(16)
    assert list(eng._prefill_keys) == [16]       # oldest bucket key dropped
    eng._get_prefill_exec(8)                     # re-derive key, cache hit
    assert list(eng._prefill_keys) == [8]


def test_cache_invalidation_reaches_warm_engine(model):
    """clear()/invalidate() on the shared cache must actually force a warm
    engine to rebuild — the engine may not serve a privately-pinned copy."""
    cfg, _ = model
    cache = ScheduleCache(capacity=16)
    eng = _engine(model, cache, warmup=False)
    eng._get_prefill_exec(8)
    builds = cache.stats.builds
    eng._get_prefill_exec(8)                     # warm: no new build
    assert cache.stats.builds == builds
    cache.clear()
    eng._get_prefill_exec(8)
    assert cache.stats.builds == builds + 1      # rebuild observed


def test_prefill_tokens_counted_separately(model, shared_cache):
    cfg, _ = model
    eng = _engine(model, shared_cache)
    for r in _reqs(cfg, 2, max_new=3):
        eng.submit(r)
    eng.run_until_drained()
    assert eng.stats.prefill_tokens == 2         # one first-token per request
    assert eng.stats.tokens_out == 4             # the remaining decode tokens
    assert Dispatcher._engine_tokens(eng.stats) == 6


def test_truncation_is_signaled(model, shared_cache):
    """ISSUE 7 satellite: a request stopped early by a full context window
    must say so — ``truncated`` set on the request, fewer tokens than
    asked, and the dispatcher's ``truncated`` counter incremented —
    instead of silently returning a short answer."""
    cfg, _ = model
    disp = Dispatcher(max_pending=16)
    disp.register_model("m", _engine(model, shared_cache, max_len=24))
    req = disp.submit("m", np.ones(16, np.int32), max_new_tokens=64)
    disp.run_until_drained()
    assert req.done and req.truncated
    assert 0 < len(req.generated) < 64     # stopped at the window, loudly
    snap = disp.snapshot()
    assert snap["truncated"] == 1
    # the untruncated path stays unflagged
    ok = disp.submit("m", np.ones(4, np.int32), max_new_tokens=2)
    disp.run_until_drained()
    assert not ok.truncated and snap["truncated"] == 1


def test_free_slots_never_negative(model, shared_cache):
    """ISSUE 7 satellite (property): across every queue/slot state a
    serving engine passes through — deep overflow queues, partial drains,
    refills — ``free_slots()`` is clamped at 0, never negative."""
    cfg, _ = model
    eng = _engine(model, shared_cache)                  # 2 slots
    states = []
    for n_queued in range(7):
        for r in _reqs(cfg, n_queued, max_new=2, seed=n_queued + 1):
            eng.submit(r)
        states.append(eng.free_slots())
        assert eng.free_slots() == max(0, 2 - len(eng.queue))
        while not eng.idle:
            eng.step()
            assert eng.free_slots() >= 0                # during drain too
    assert min(states) == 0 and max(states) == 2        # both regimes hit


def test_retire_fails_queued_requests_loudly(model, shared_cache):
    """ISSUE 7 satellite: retire() with directly-submitted requests still
    queued must complete them as failed (error + ``on_complete``), not
    silently vanish them — the direct-submit retire race."""
    cfg, _ = model
    eng = _engine(model, shared_cache)
    seen = []
    reqs = _reqs(cfg, 3, max_new=2)
    for r in reqs:
        r.on_complete = lambda model_name, req: seen.append(req.rid)
        eng.submit(r)                  # never stepped: all three queued
    eng.retire()
    assert not eng.queue
    for r in reqs:
        assert r.done and r.error      # failed, not dropped
        assert "retired" in r.error
    assert sorted(seen) == [0, 1, 2]   # every callback fired
    with pytest.raises(RuntimeError):
        eng.validate_request(_reqs(cfg, 1)[0])


def test_unservable_direct_submit_fails_request_not_stepper(model, shared_cache):
    """ISSUE 7 satellite: an unservable prompt submitted straight to the
    engine (skipping dispatcher validation) must fail THAT request with
    an error — not raise on the stepping thread (poisoning every tenant)
    or lose the already-popped request."""
    cfg, _ = model
    eng = _engine(model, shared_cache)
    bad = Request(rid=9, prompt=np.zeros(17, np.int32), max_new_tokens=2)
    good = _reqs(cfg, 1, max_new=2)[0]
    eng.submit(bad)
    eng.submit(good)
    finished = eng.run_until_drained()          # must not raise
    assert bad in finished and bad.done
    assert bad.error and "unservable" in bad.error
    assert good.done and not good.error         # queue kept flowing
    assert len(good.generated) == 2


def test_direct_engine_submit_reaches_ready_index(model, shared_cache):
    """ISSUE 7 carry-over: the engine-side submit hook makes direct
    ``engine.submit()`` work visible to the dispatcher's indexed ready
    set, so pool grants (and the composer's refill) can see it."""
    cfg, _ = model
    disp = Dispatcher(max_pending=16)
    disp.register_model("m", _engine(model, shared_cache))
    assert disp.active_lanes() == []
    disp.engine("m").submit(_reqs(cfg, 1, max_new=2)[0])
    assert disp.active_lanes() == ["m"]         # hook indexed the lane
    disp.run_until_drained()
    assert disp.active_lanes() == []


def test_dispatcher_matches_direct_engine(model, shared_cache):
    """Token-identical outputs: dispatcher multiplexing vs direct serving."""
    cfg, _ = model
    direct = _engine(model, shared_cache)
    for r in _reqs(cfg, 5, seed=3):
        direct.submit(r)
    ref = {r.rid: r.generated for r in direct.run_until_drained()}

    disp = Dispatcher(max_pending=16)
    disp.register_model("m", _engine(model, shared_cache))
    for r in _reqs(cfg, 5, seed=3):
        disp.submit_request("m", r)
    got = {r.rid: r.generated for r in disp.run_until_drained()}
    assert got == ref
    assert disp.snapshot()["requests_done"] == 5


def _greedy_reference(model, prompt, max_new, pad_to=32):
    """Greedy tokens from full-sequence forwards over the growing sequence
    (padded to one length: attention is causal, so padding is not seen)."""
    cfg, params = model
    fwd = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg)[0])
    seq, out = list(prompt), []
    for _ in range(max_new):
        tokens = np.zeros((1, pad_to), np.int32)
        tokens[0, :len(seq)] = seq
        logits = np.asarray(fwd(params, tokens))[0, len(seq) - 1, : cfg.vocab]
        out.append(int(np.argmax(logits)))
        seq.append(out[-1])
    return out


def test_step_donates_the_previous_cache(model, shared_cache):
    """Each step's programs take the KV cache as a donated argument: the
    buffers the engine held before ``step()`` are deleted after it, and
    the greedy tokens still match full-sequence forwards."""
    cfg, _ = model
    eng = _engine(model, shared_cache)
    reqs = _reqs(cfg, 3, max_new=5, seed=4)
    reqs[1].prompt = reqs[1].prompt[:3]           # slots at other offsets
    for r in reqs:
        eng.submit(r)
    steps = 0
    while not eng.idle:
        before = jax.tree_util.tree_leaves(eng.kv_cache)
        eng.step()
        steps += 1
        assert all(leaf.is_deleted() for leaf in before)
        assert not any(
            leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(eng.kv_cache))
    assert steps > 1
    for r in reqs:
        assert r.generated == _greedy_reference(model, r.prompt, r.max_new_tokens)


def test_engine_spans_bracket_the_host_work(model, shared_cache):
    """Each admission opens prefill (with engine.h2d inside it), then
    engine.readback; each decode step opens decode (h2d inside), then its
    readback; each finish opens engine.finish.  t_first comes after the
    first token's read-back."""
    from repro.obs import SpanTracer

    cfg, _ = model
    tr = SpanTracer().enable()
    try:
        eng = _engine(model, shared_cache, tracer=tr)
        reqs = _reqs(cfg, 2, max_new=3)
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
    finally:
        tr.disable()
    spans = [e for e in tr.drain() if e.ph == "X" and e.cat == "engine"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e.name, []).append(e)
    assert len(by_name["prefill"]) == 2 and len(by_name["engine.finish"]) == 2
    assert len(by_name["decode"]) >= 2
    assert len(by_name["engine.h2d"]) == len(by_name["prefill"]) + len(by_name["decode"])
    kinds = [e.args["kind"] for e in by_name["engine.readback"]]
    assert kinds.count("prefill") == 2 and kinds.count("decode") == len(by_name["decode"])
    for outer in by_name["prefill"] + by_name["decode"]:
        inner = [h for h in by_name["engine.h2d"]
                 if outer.ts <= h.ts and h.ts + h.dur <= outer.ts + outer.dur]
        assert len(inner) == 1
    assert {e.rid for e in by_name["engine.finish"]} == {r.rid for r in reqs}
    reads = [e for e in by_name["engine.readback"] if e.args["kind"] == "prefill"]
    for req, pre, rb in zip(reqs, by_name["prefill"], reads):
        assert pre.rid == req.rid
        assert pre.ts + pre.dur <= rb.ts
        assert req.t_first >= rb.ts + rb.dur


def test_sealed_programs_carry_their_body_names(model, shared_cache):
    """The device trace names a run after its program: jit_decode_body and
    jit_prefill_body, not jit__unknown."""
    eng = _engine(model, shared_cache)
    assert eng._decode.as_text().startswith("HloModule jit_decode_body")
    assert eng._get_prefill_exec(8).as_text().startswith("HloModule jit_prefill_body")
