"""Compile the main path's kernels and step programs for a TPU v5e.

Nothing runs: each program is lowered from shapes and compiled for a
described ``v5e:2x2`` topology by the TPU compiler, which refuses what the
chip would refuse (tiling, fast-memory limits, programs that do not fit).
The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around these compiles (a compile for
a described chip cannot be read back without one).
"""

from __future__ import annotations

import functools
import json
import math
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as C
from repro.kernels.flash_attention import flash_attention
from repro.kernels.stream_pack import stream_pack_matmul
from repro.models import abstract_model, init_cache
from repro.serving.engine import (
    decode_body, decode_program, prefill_body, prefill_program,
)

HBM_BYTES = 16 * 10**9          # one v5e chip
SLOTS, MAX_LEN, BUCKET = 8, 1024, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, sharding), tree)


def _footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("bh,bh_kv,seq,hd", [
    (32, 32, 1024, 64),      # stablelm-1.6b: 32 heads x 64
    (32, 8, 2048, 128),      # GQA: 32 query heads over 8 kv heads x 128
])
def test_flash_attention_compiles_to_a_tpu_kernel(one_chip, bh, bh_kv, seq, hd):
    q = _sds((bh, seq, hd), jnp.bfloat16, one_chip)
    kv = _sds((bh_kv, seq, hd), jnp.bfloat16, one_chip)
    fn = functools.partial(flash_attention, group=bh // bh_kv)
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stream_pack_matmul_compiles_to_a_tpu_kernel(one_chip):
    x = _sds((4, 512, 2048), jnp.bfloat16, one_chip)
    w = _sds((4, 2048, 5632), jnp.bfloat16, one_chip)
    compiled = jax.jit(stream_pack_matmul).lower(x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def stablelm(one_chip):
    """Full-width stablelm-1.6b params and serving KV cache, as shapes."""
    cfg = C.get("stablelm-1.6b")
    params, _ = abstract_model(cfg)
    cache = jax.eval_shape(lambda: init_cache(cfg, SLOTS, MAX_LEN))
    return cfg, _on(params, one_chip), _on(cache, one_chip)


def test_stablelm_decode_step_fits_one_chip(one_chip, stablelm):
    cfg, params, cache = stablelm
    tokens = _sds((SLOTS, 1), jnp.int32, one_chip)
    compiled = jax.jit(functools.partial(decode_body, cfg=cfg)).lower(
        params, cache, tokens).compile()
    assert _footprint(compiled) < HBM_BYTES


def test_stablelm_prefill_bucket_fits_one_chip(one_chip, stablelm):
    cfg, params, cache = stablelm
    scalar = _sds((), jnp.int32, one_chip)
    compiled = jax.jit(functools.partial(prefill_body, cfg=cfg)).lower(
        params, _sds((1, BUCKET), jnp.int32, one_chip), cache, scalar, scalar,
    ).compile()
    assert _footprint(compiled) < HBM_BYTES


# --- the engine's sealed steps at the benchmark's engine sizes -------------

BENCH_CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"
# an instruction's name and the first array shape it produces
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = \(?\w+\[([\d,]*)\]")


def _copies_of(compiled, elements: int) -> list:
    """Names of ``copy`` / ``copy*fusion`` ops producing ``elements`` values."""
    found = []
    for line in compiled.as_text().splitlines():
        m = _HLO_OP.match(line)
        if m and m.group(1).startswith("copy") and m.group(2):
            if math.prod(int(d) for d in m.group(2).split(",")) == elements:
                found.append(m.group(1))
    return found


@pytest.fixture(scope="module", params=["stablelm-1.6b", "phi4-mini-3.8b"])
def served(request, one_chip):
    """A benchmark configuration's params and KV cache, as shapes, at its
    engine's slots and length."""
    conf = json.loads((BENCH_CONFIGS / f"{request.param}.json").read_text())
    cfg = C.ModelConfig(**conf["model"])
    eng = conf["engine"]
    params, _ = abstract_model(cfg)
    cache = jax.eval_shape(
        lambda: init_cache(cfg, eng["max_slots"], eng["max_len"]))
    return cfg, eng, _on(params, one_chip), _on(cache, one_chip)


def _assert_cache_updated_in_place(compiled, cache):
    kv = (cache["k"], cache["v"])
    for leaf in kv:
        assert _copies_of(compiled, math.prod(leaf.shape)) == []
    kv_bytes = sum(math.prod(leaf.shape) * leaf.dtype.itemsize for leaf in kv)
    assert compiled.memory_analysis().alias_size_in_bytes >= kv_bytes


def test_sealed_decode_writes_the_cache_in_place(one_chip, served):
    cfg, eng, params, cache = served
    tokens = _sds((eng["max_slots"], 1), jnp.int32, one_chip)
    compiled = decode_program(cfg).lower(params, cache, tokens).compile()
    _assert_cache_updated_in_place(compiled, cache)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_sealed_prefill_writes_the_cache_in_place(one_chip, served):
    cfg, eng, params, cache = served
    scalar = _sds((), jnp.int32, one_chip)
    tokens = _sds((1, max(eng["buckets"])), jnp.int32, one_chip)
    compiled = prefill_program(cfg).lower(
        params, tokens, cache, scalar, scalar).compile()
    _assert_cache_updated_in_place(compiled, cache)


# --- the latent cache of the DeepSeek-V2 configuration ----------------------


@pytest.fixture(scope="module")
def latent(one_chip):
    """``deepseek-v2-ep20``'s params and latent cache, as shapes, at its
    engine's 64 slots x 4096 positions (9 layers, 8 held experts)."""
    conf = json.loads((BENCH_CONFIGS / "deepseek-v2-ep20.json").read_text())
    model = dict(conf["model"])
    model["mla"] = C.MLAConfig(**model["mla"])
    model["moe"] = C.MoEConfig(**{**model["moe"],
                                  "experts_held": tuple(model["moe"]["experts_held"])})
    model["rope_scaling"] = C.RopeScaling(**model["rope_scaling"])
    cfg = C.ModelConfig(**model)
    eng = conf["engine"]
    params, _ = abstract_model(cfg)
    cache = jax.eval_shape(
        lambda: init_cache(cfg, eng["max_slots"], eng["max_len"]))
    return cfg, eng, _on(params, one_chip), _on(cache, one_chip)


def _assert_latent_cache_updated_in_place(compiled, cache):
    rows = (cache["ckv"], cache["krope"])
    for leaf in rows:
        assert leaf.shape[:3] == (9, 64, 4096)
        assert _copies_of(compiled, math.prod(leaf.shape)) == []
    nbytes = sum(math.prod(leaf.shape) * leaf.dtype.itemsize for leaf in rows)
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes
    assert _footprint(compiled) < HBM_BYTES


def test_sealed_latent_decode_writes_the_cache_in_place(one_chip, latent):
    cfg, eng, params, cache = latent
    tokens = _sds((eng["max_slots"], 1), jnp.int32, one_chip)
    compiled = decode_program(cfg).lower(params, cache, tokens).compile()
    _assert_latent_cache_updated_in_place(compiled, cache)


def test_sealed_latent_prefill_writes_the_cache_in_place(one_chip, latent):
    cfg, eng, params, cache = latent
    scalar = _sds((), jnp.int32, one_chip)
    tokens = _sds((1, max(eng["buckets"])), jnp.int32, one_chip)
    compiled = prefill_program(cfg).lower(
        params, tokens, cache, scalar, scalar).compile()
    _assert_latent_cache_updated_in_place(compiled, cache)
