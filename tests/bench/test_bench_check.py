"""``correct`` must come out false where the output is wrong: the float8
control in the program's place, and the faults a serving cell can have,
planted under a whole run at smoke size on the CPU."""

from __future__ import annotations

import time
from types import SimpleNamespace

import _bench_smoke as S
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serving.engine as engine_mod
from bench import check, run, spec

pytestmark = pytest.mark.timeout(300)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return S.write_root(tmp_path_factory.mktemp("bench"), {"chat": S.open_mix()})


@pytest.mark.parametrize("seed", [3, 2**31 + 9, 2**34 + 1])
def test_control_fails_the_limit_that_the_program_meets(root, seed):
    cell = spec.load_cell("chat", root)
    runs = {control: run.run_cell(cell, seed, 1.5, False, jax.devices()[:1], root=root,
                                  t_start=time.perf_counter(), control=control)
            for control in (False, True)}
    assert all(r["checked"]["tokens"] >= 60 for r in runs.values())
    assert runs[False]["correct"] is True
    assert runs[True]["correct"] is False
    program, control = (runs[c]["compared"]["logit_gap_max"]["value"] for c in (False, True))
    assert program <= S.SMOKE_LIMIT < control


def _altered_token(params, cache, tokens, *, cfg):
    nxt, cache = engine_mod_decode(params, cache, tokens, cfg=cfg)
    return nxt.at[0, 0].set((nxt[0, 0] + 1) % cfg.vocab), cache


def _state_unchanged(params, cache, tokens, *, cfg):
    nxt, _ = engine_mod_decode(params, cache, tokens, cfg=cfg)
    return nxt, jax.tree.map(jnp.copy, cache)


engine_mod_decode = engine_mod.decode_body


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_a_broken_decode_step_reads_not_correct(root, monkeypatch, fault):
    monkeypatch.setattr(engine_mod, "decode_body", fault)
    cell = spec.load_cell("chat", root)
    res = run.run_cell(cell, 17, 1.5, False, jax.devices()[:1], root=root,
                       t_start=time.perf_counter())
    assert res["correct"] is False
    assert res["compared"]["logit_gap_max"]["value"] > S.SMOKE_LIMIT


def _done(lane: str, served: int):
    return SimpleNamespace(lane=lane, req=SimpleNamespace(prompt=[0] * 10, generated=[0] * served))


@pytest.mark.parametrize("seed", [0, 5, 2**40 + 3])
def test_the_sample_compares_a_request_of_every_lane(seed):
    # at the committed sizes of the four-lane cell: replica0 served the
    # longest request (256 tokens) and most others; each other replica's
    # requests serve 64-128 tokens, so the longest and two of them alone
    # would reach the token target before the fourth lane
    chk = spec.load_cell("stablelm-chat-x4").config["check"]
    target, most = chk["sample_tokens"], chk["sample_requests"]
    rng = np.random.default_rng(seed)
    done = ([_done("replica0", 256)] + [_done("replica0", 200) for _ in range(20)]
            + [_done(f"replica{k}", int(n)) for k in (1, 2, 3)
               for n in rng.integers(64, 129, 5)])
    picks = check.sample(done, seed, target, most)
    assert picks[0] is done[0]
    assert {p.lane for p in picks} == {f"replica{k}" for k in range(4)}
    assert len(picks) == len(set(map(id, picks))) <= most
    # every lane is read even where the target or the count is already met
    assert {p.lane for p in check.sample(done, seed, 1, 1)} == {f"replica{k}" for k in range(4)}
    # on one lane the sample is the longest, then the seeded order
    one = [_done("replica0", n) for n in (5, 9, 30, 7, 11, 3)]
    rest = [s for s in one if s is not one[2]]
    order = np.random.default_rng((seed ^ check.SAMPLE_SALT) % 2**64).permutation(len(rest))
    want, tokens = [one[2]], 30
    for i in order:
        if tokens >= 45:
            break
        want.append(rest[i])
        tokens += len(rest[i].req.generated)
    assert check.sample(one, seed, 45, 24) == want
