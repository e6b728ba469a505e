"""Pallas kernel tests: sweep shapes/dtypes, assert_allclose vs ref.py
oracles (interpret=True executes the kernel body on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention import flash_attention, flash_attention_ref, mha_flash
from repro.kernels.stream_pack import (
    packed_branches,
    stream_pack,
    stream_pack_matmul,
    stream_pack_matmul_ref,
)

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape, dtype=np.float32)
    return jnp.asarray(x, dtype=dtype)


# ---------------------------------------------------------------------------
# stream_pack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lanes", [1, 2, 7])
@pytest.mark.parametrize("mkn", [(16, 16, 16), (64, 32, 16), (128, 128, 128), (256, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stream_pack_shapes_dtypes(lanes, mkn, dtype):
    M, K, N = mkn
    x = _rand((lanes, M, K), dtype)
    w = _rand((lanes, K, N), dtype)
    got = stream_pack_matmul(x, w, interpret=True)
    ref = stream_pack_matmul_ref(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("blocks", [(16, 16, 16), (32, 64, 16), (64, 32, 32)])
def test_stream_pack_block_sweep(blocks):
    bm, bn, bk = blocks
    x = _rand((3, 64, 64), jnp.float32)
    w = _rand((3, 64, 64), jnp.float32)
    got = stream_pack_matmul(x, w, block_m=bm, block_n=bn, block_k=bk, interpret=True)
    ref = stream_pack_matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_stream_pack_rejects_misaligned():
    x = _rand((2, 96, 64), jnp.float32)
    w = _rand((2, 64, 64), jnp.float32)
    with pytest.raises(ValueError):
        stream_pack_matmul(x, w, block_m=64, interpret=True)


def test_packed_branches_list_api():
    xs = [_rand((32, 16), jnp.float32) for _ in range(5)]
    ws = [_rand((16, 8), jnp.float32) for _ in range(5)]
    outs = packed_branches(xs, ws, interpret=True)
    for x, w, o in zip(xs, ws, outs):
        np.testing.assert_allclose(np.asarray(o), np.asarray(x @ w), rtol=1e-5, atol=1e-5)


@given(
    lanes=st.integers(1, 4),
    m=st.sampled_from([16, 32, 64]),
    k=st.sampled_from([16, 32]),
    n=st.sampled_from([16, 32]),
)
@settings(max_examples=25, deadline=None)
def test_stream_pack_property(lanes, m, k, n):
    x = _rand((lanes, m, k), jnp.float32)
    w = _rand((lanes, k, n), jnp.float32)
    got = stream_pack_matmul(x, w, interpret=True)
    ref = stream_pack_matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq", [64, 128, 256])
@pytest.mark.parametrize("hd", [32, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_causal_shapes(seq, hd, dtype):
    q = _rand((4, seq, hd), dtype)
    k = _rand((4, seq, hd), dtype)
    v = _rand((4, seq, hd), dtype)
    got = flash_attention(q, k, v, interpret=True, block_q=64, block_kv=64)
    ref = flash_attention_ref(q, k, v)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("group", [1, 2, 4])
def test_flash_gqa_groups(group):
    NKV, S, hd = 2, 128, 32
    q = _rand((NKV * group, S, hd), jnp.float32)
    k = _rand((NKV, S, hd), jnp.float32)
    v = _rand((NKV, S, hd), jnp.float32)
    got = flash_attention(q, k, v, group=group, interpret=True, block_q=64, block_kv=64)
    ref = flash_attention_ref(q, k, v, group=group)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_sliding_window(window):
    q = _rand((2, 256, 32), jnp.float32)
    k = _rand((2, 256, 32), jnp.float32)
    v = _rand((2, 256, 32), jnp.float32)
    got = flash_attention(q, k, v, window=window, interpret=True, block_q=64, block_kv=64)
    ref = flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("softcap", [20.0, 50.0])
def test_flash_softcap(softcap):
    q = _rand((2, 128, 32), jnp.float32)
    k = _rand((2, 128, 32), jnp.float32)
    v = _rand((2, 128, 32), jnp.float32)
    got = flash_attention(q, k, v, softcap=softcap, interpret=True, block_q=64, block_kv=64)
    ref = flash_attention_ref(q, k, v, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_bidirectional():
    q = _rand((2, 128, 32), jnp.float32)
    k = _rand((2, 128, 32), jnp.float32)
    v = _rand((2, 128, 32), jnp.float32)
    got = flash_attention(q, k, v, causal=False, interpret=True, block_q=64, block_kv=64)
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_cross_lengths():
    """Sq != Skv (cross attention / cached prefill)."""
    q = _rand((2, 64, 32), jnp.float32)
    k = _rand((2, 256, 32), jnp.float32)
    v = _rand((2, 256, 32), jnp.float32)
    got = flash_attention(
        q, k, v, causal=False, interpret=True, block_q=64, block_kv=64
    )
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_mha_flash_model_layout_matches_model_attention():
    """The jit wrapper must agree with the model's reference _sdpa path."""
    import repro.configs as C
    from repro.models.layers import _sdpa

    cfg = C.get("gemma2-27b", smoke=True)
    B, S, NH, NKV, hd = 2, 64, 4, 2, 32
    q = _rand((B, S, NH, hd), jnp.float32)
    k = _rand((B, S, NKV, hd), jnp.float32)
    v = _rand((B, S, NKV, hd), jnp.float32)
    got = mha_flash(q, k, v, softcap=50.0, window=16, interpret=True)
    ref = _sdpa(
        q, k, v, scale=1.0 / np.sqrt(hd), softcap_val=50.0,
        q_pos=jnp.arange(S), kv_pos=jnp.arange(S), window=16, kv_valid=None,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("call", [
    lambda: mha_flash(*(_rand((1, 64, 2, 32), jnp.float32) for _ in range(3))),
    lambda: stream_pack(_rand((2, 64, 64), jnp.float32),
                        _rand((2, 64, 64), jnp.float32)),
])
def test_compiled_kernel_off_tpu_raises_not_falls_back(call):
    """use_kernel=True without interpret=True on a non-TPU backend must
    raise, never quietly run the jnp oracle in the kernel's place."""
    assert jax.default_backend() != "tpu"
    with pytest.raises(ValueError, match="needs a TPU"):
        call()
