"""The dense architecture module against values recorded before the
harness reached it through ``bench/arch/``: the seeded weights, the
reference's logits (float32 and the float8 control) and the work counts
must stay bit for bit what the benchmark measured with; and a
configuration's nested sections reach the program as its dataclasses."""

from __future__ import annotations

import dataclasses
import hashlib
import json

import _bench_smoke as S
import jax
import numpy as np
import pytest

from bench import serve, spec, weights
from bench.arch import dense
from repro.configs import deepseek_v2_236b, xlstm_125m, zamba2_2_7b

SEED = 2**40 + 7
TOKENS = np.random.default_rng(5).integers(0, 512, (3, 20)).astype(np.int32)
ROWS, COLS = np.array([0, 0, 1, 2, 2]), np.array([0, 7, 19, 3, 12])

# sha256 (first 16 hex digits) of each leaf's and each logits array's
# bytes, and the (FLOPs, bytes) counts, recorded on the CPU from the
# harness as it was before the architecture modules
PINNED = {
    False: {
        "leaves": {
            "['embed']['tok']": "cec89e78a367b9ba",
            "['embed']['unembed']": "aff583fb7c253866",
            "['final_norm']['bias']": "edaa9ffd578c3d3e",
            "['final_norm']['scale']": "7d8d339cba14975f",
            "['layers']['attn']['wk']": "90a8eff3bb611788",
            "['layers']['attn']['wo']": "f58ede0fba584c68",
            "['layers']['attn']['wq']": "c5a982c7ed7202e5",
            "['layers']['attn']['wv']": "0b09d5a2e2e5d7d2",
            "['layers']['ffn']['w_down']": "0d0990b3c5d5a23d",
            "['layers']['ffn']['w_gate']": "f609709b23c90bb3",
            "['layers']['ffn']['w_up']": "6fcf359b871ad429",
            "['layers']['ln1']['bias']": "b4334850a2135fc6",
            "['layers']['ln1']['scale']": "ad7e11fc499a3faf",
            "['layers']['ln2']['bias']": "ff7c690e9810fe04",
            "['layers']['ln2']['scale']": "222b4e8c748f7cfc",
        },
        "logits": {
            "float32": "6249fc4f96410d33",
            "fp8": "b3577da777f1166a",
        },
        "decode": (1606656, 808960),
        "prefill": (22674432, 754432),
    },
    True: {
        "leaves": {
            "['embed']['tok']": "cec89e78a367b9ba",
            "['final_norm']['bias']": "77a4294076ab9f01",
            "['final_norm']['scale']": "9fe91b1f926ac51d",
            "['layers']['attn']['wk']": "90a8eff3bb611788",
            "['layers']['attn']['wo']": "f58ede0fba584c68",
            "['layers']['attn']['wq']": "c5a982c7ed7202e5",
            "['layers']['attn']['wv']": "0b09d5a2e2e5d7d2",
            "['layers']['ffn']['w_down']": "0d0990b3c5d5a23d",
            "['layers']['ffn']['w_gate']": "f609709b23c90bb3",
            "['layers']['ffn']['w_up']": "6fcf359b871ad429",
            "['layers']['ln1']['bias']": "82ec3d7ede0ba287",
            "['layers']['ln1']['scale']": "5e3d752a8d268664",
            "['layers']['ln2']['bias']": "3f8e25e2b4f24864",
            "['layers']['ln2']['scale']": "34d31c148ddb39ba",
        },
        "logits": {
            "float32": "ba5863c110bb5da8",
            "fp8": "fe1eb11159d86f11",
        },
        "decode": (1606656, 808960),
        "prefill": (22674432, 754432),
    },
}


def _digest(x) -> str:
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("tie", [False, True])
def test_dense_weights_are_bit_identical(tie):
    params = weights.make(dense.layout(S.smoke_model(tie)), SEED)
    got = {jax.tree_util.keystr(p): _digest(x)
           for p, x in jax.tree_util.tree_leaves_with_path(params)}
    assert got == PINNED[tie]["leaves"]


@pytest.mark.parametrize("mode", ["float32", "fp8"])
@pytest.mark.parametrize("tie", [False, True])
def test_dense_reference_logits_are_bit_identical(tie, mode):
    model = S.smoke_model(tie)
    params = weights.make(dense.layout(model), SEED)
    logits = dense.logits_at(params, model, TOKENS, ROWS, COLS, mode=mode, block=2)
    assert _digest(logits) == PINNED[tie]["logits"][mode]


@pytest.mark.parametrize("tie", [False, True])
def test_dense_work_counts_are_unchanged(tie):
    shapes = dense.Shapes.from_model(S.smoke_model(tie))
    assert shapes.decode([100, 59]) == PINNED[tie]["decode"]
    assert shapes.prefill(37) == PINNED[tie]["prefill"]


# moe and mla; ssm; xlstm, whose slstm_at is a tuple
@pytest.mark.parametrize("config", [deepseek_v2_236b, zamba2_2_7b, xlstm_125m],
                         ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_nested_sections_become_the_program_dataclasses(config):
    want = config.smoke_config()
    model = json.loads(json.dumps(dataclasses.asdict(want)))     # as a file holds it
    got = serve.model_config(model)
    assert got == want and repr(got) == repr(want)
    for section in ("moe", "mla", "ssm", "xlstm"):
        assert type(getattr(got, section)) is type(getattr(want, section))
    dense_cfg = serve.model_config(spec.load_cell("stablelm-chat").model)
    assert dense_cfg.moe is None and dense_cfg.mla is None
