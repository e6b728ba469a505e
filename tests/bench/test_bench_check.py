"""``correct`` must come out false where the output is wrong: the float8
control in the program's place, and the faults a serving cell can have,
planted under a whole run at smoke size on the CPU."""

from __future__ import annotations

import time

import _bench_smoke as S
import jax
import jax.numpy as jnp
import pytest

import repro.serving.engine as engine_mod
from bench import run, spec

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
