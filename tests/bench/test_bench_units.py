"""The benchmark's yardstick on the CPU: work counts, peaks, weights,
reference, traffic generation."""

from __future__ import annotations

import dataclasses
import itertools
import json
import math
import time

import _bench_smoke as S  # noqa: F401  (puts the repo on sys.path)
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import generator, serve, spec, weights, work
from bench.arch import dense
from bench.peaks import PEAKS, peaks_for
from repro.configs.base import ModelConfig
from repro.models import forward, init_model
from repro.models.transformer import abstract_model


def _leaf_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("tie,norm", [(False, "layernorm"), (True, "rmsnorm")])
def test_param_bytes_match_init_model(tie, norm):
    model = S.smoke_model(tie)
    model["norm"] = norm
    cfg = ModelConfig(**model)
    params, _ = init_model(jax.random.key(0), cfg)
    assert dense.Shapes.from_model(model).param_bytes() == _leaf_bytes(params)


@pytest.mark.parametrize("tie", [False, True])
def test_bench_weights_have_the_engine_layout(tie):
    model = S.smoke_model(tie)
    made = weights.make(dense.layout(model), 2**40 + 3)
    want, _ = abstract_model(ModelConfig(**model))
    shape = lambda t: jax.tree.map(lambda x: (x.shape, x.dtype), t)
    assert shape(made) == shape(want)
    again = weights.make(dense.layout(model), 2**40 + 3)
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree_util.tree_leaves(made), jax.tree_util.tree_leaves(again)))


@pytest.mark.parametrize("name", ["stablelm-1.6b", "phi4-mini-3.8b"])
def test_committed_configs_pin_the_published_shapes(name):
    cfg = json.loads((S.REPO / f"bench/configs/{name}.json").read_text())
    model, pub = cfg["model"], cfg["published"]
    ModelConfig(**model)                       # every key is a program field
    assert model["d_model"] == pub["hidden_size"]
    assert model["d_ff"] == pub["intermediate_size"]
    assert model["n_heads"] == pub["num_attention_heads"]
    assert model["n_kv_heads"] == pub["num_key_value_heads"]
    assert model["n_layers"] == pub["num_hidden_layers"]
    assert model["vocab"] == pub["vocab_size"]
    assert model["tie_embeddings"] == pub["tie_word_embeddings"]
    assert model["norm_eps"] == pub.get("layer_norm_eps", pub.get("rms_norm_eps"))


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks_for("TPU v99")
    assert peaks_for("TPU v5 lite").hbm_bytes_per_s == 819e9


@pytest.mark.parametrize("flops,nbytes", [(1e12, 1e6), (1e6, 1e9)])
def test_step_timed_at_its_bound_reads_100(flops, nbytes):
    peaks = PEAKS["TPU v5 lite"]
    t = work.roofline_seconds(flops, nbytes, peaks)
    assert work.roofline_pct(flops, nbytes, t, peaks) == pytest.approx(100.0)
    assert work.roofline_pct(flops, nbytes, 2 * t, peaks) == pytest.approx(50.0)


def test_decode_and_prefill_counts():
    s = dense.Shapes.from_model(S.smoke_model())
    assert s.decode([]) == (0, 0)
    f1, b1 = s.decode([10])
    f2, b2 = s.decode([10, 20])
    kv = s.kv_bytes_per_position
    assert b2 - b1 == 21 * kv + s.d_model * s.dtype_bytes     # positions + write + embed row
    assert f2 > 2 * f1 - 2 * (s.layer_matrix_params + s.head_params)
    fp, bp = s.prefill(16)
    assert fp == 2 * s.layer_matrix_params * 16 + 2 * s.head_params + \
        4 * s.n_layers * s.n_heads * s.head_dim * 16 * 17 // 2
    assert bp == s.prefill(1)[1] + 15 * (kv + s.d_model * s.dtype_bytes)


@pytest.mark.parametrize("tie", [False, True])
def test_reference_matches_the_program_in_float32(tie):
    model = dict(S.smoke_model(tie), dtype="float32")
    cfg = ModelConfig(**model)
    params = weights.make(dense.layout(model), 11)
    toks = np.random.default_rng(0).integers(0, cfg.vocab, (3, 24)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = forward(params, {"tokens": jnp.asarray(toks)}, cfg)
    want = np.asarray(want[..., : cfg.vocab])
    rows, cols = np.repeat(np.arange(3), 24), np.tile(np.arange(24), 3)
    got = np.asarray(dense.logits_at(params, model, toks, rows, cols, block=2))
    np.testing.assert_allclose(got.reshape(want.shape), want, atol=1e-4, rtol=1e-4)


def _take(mix, seed, n, vocab=512):
    return list(itertools.islice(generator.requests(mix, seed, vocab), n))


def test_same_seed_same_schedule_and_same_work_on_every_seed():
    mix = S.open_mix()
    a, b, c = _take(mix, 2**33 + 1, 64), _take(mix, 2**33 + 1, 64), _take(mix, 5, 64)
    assert [(x.due_s, x.max_new, x.prompt.tolist()) for x in a] == \
        [(x.due_s, x.max_new, x.prompt.tolist()) for x in b]
    assert [x.due_s for x in a] != [x.due_s for x in c]
    block = mix["block"]
    for i in range(0, 64, block):             # one block: same lengths, new order
        assert sorted(len(x.prompt) for x in a[i:i + block]) == \
            sorted(len(x.prompt) for x in c[i:i + block])
        assert sorted(x.max_new for x in a[i:i + block]) == \
            sorted(x.max_new for x in c[i:i + block])


def test_a_fixed_order_leaves_only_the_tokens_to_the_seed():
    mix = dict(S.open_mix(), order_seed=12345)
    a, b = _take(mix, 1, 48), _take(mix, 2**35 + 3, 48)
    assert [(x.due_s, len(x.prompt), x.max_new) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new) for x in b]
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in b]


@pytest.mark.parametrize("cell", ["stablelm-chat", "phi4mini-batch", "stablelm-rag",
                                  "stablelm-chat-x4"])
def test_committed_mixes_stay_in_their_clips_and_fit(cell):
    c = spec.load_cell(cell)
    mix = c.traffic
    items = _take(mix, 99, 4 * mix["block"], c.model["vocab"])
    buckets = c.engine["buckets"]
    generator.check_fits(mix, c.engine["max_len"], buckets)
    for it in items:
        p = len(it.prompt)
        assert mix["prompt"]["min"] <= p <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= it.max_new <= mix["output"]["max"]
        assert p + it.max_new <= c.engine["max_len"] - 1
        assert any(b >= p for b in buckets)
        assert int(it.prompt.max()) < c.model["vocab"]


def test_fit_check_refuses_what_cannot_be_served():
    mix = S.open_mix()
    with pytest.raises(ValueError):
        generator.check_fits(mix, 128, [16, 32])           # prompt 60 > bucket 32
    with pytest.raises(ValueError):
        generator.check_fits(mix, 96, [64])                # 60 + 40 > 95


def test_poisson_rate_within_sampling_tolerance():
    # gaps come in blocks of the exponential's stratum means, so the mean
    # gap over whole blocks is 1/rate up to rounding; 20 blocks, 1%
    mix = S.open_mix(rate=12.5)
    items = _take(mix, 3, 20 * mix["block"] + 1)
    rate = (len(items) - 1) / items[-1].due_s
    assert rate == pytest.approx(12.5, rel=0.01)


def test_closed_loop_never_lets_the_backlog_fall_below_its_floor():
    rng = np.random.default_rng(0)
    outstanding, floor = 0, 16
    for _ in range(200):
        outstanding += generator.closed_refill(outstanding, floor)
        assert outstanding >= floor
        outstanding -= int(rng.integers(0, 5))
    assert generator.closed_refill(20, 16) == 0


def test_first_token_time_is_taken_when_the_token_reaches_the_list():
    tokens = serve.Tokens()
    assert tokens.t_first == 0.0
    before = time.perf_counter()
    tokens.append(5)
    first = tokens.t_first
    tokens.append(6)
    assert before <= first <= time.perf_counter()
    assert tokens.t_first == first and tokens == [5, 6]
