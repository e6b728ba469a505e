"""``layers.append_kv`` against a plain per-slot reference: each slot's new
K/V land at that slot's own offset, clamped as ``dynamic_update_slice``
clamps, in every layer, and nothing else in the cache changes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.layers import append_kv

L, B, T, HD = 3, 4, 16, 8


def _reference(cache, new, pos):
    out = np.array(cache)
    s_new = new.shape[2]
    for b in range(cache.shape[1]):
        at = min(max(int(pos[b]), 0), cache.shape[2] - s_new)
        out[:, b, at:at + s_new] = new[:, b]
    return out


@pytest.mark.parametrize("nkv", [4, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("s_new", [1, 7])
@pytest.mark.parametrize("last", ["at_end", "past_end"])
def test_append_kv_matches_per_slot_reference(nkv, s_new, last):
    rng = np.random.default_rng(nkv * 100 + s_new)
    shape = (L, B, T, nkv, HD)
    cache_k = rng.standard_normal(shape).astype(jnp.bfloat16)
    cache_v = rng.standard_normal(shape).astype(jnp.bfloat16)
    new_k = rng.standard_normal((L, B, s_new, nkv, HD)).astype(np.float32)
    new_v = rng.standard_normal((L, B, s_new, nkv, HD)).astype(np.float32)
    # distinct offsets: a slot at 0, one at the last offset that fits (or
    # past it, where the write clamps to T - s_new), two in between
    end = T - s_new if last == "at_end" else T - 1
    pos = np.array([3, 0, end, 5], np.int32)

    ck, cv = jax.jit(append_kv)(cache_k, cache_v, new_k, new_v, pos)

    assert ck.dtype == cache_k.dtype and ck.shape == shape
    np.testing.assert_array_equal(
        np.asarray(ck), _reference(cache_k, new_k.astype(jnp.bfloat16), pos))
    np.testing.assert_array_equal(
        np.asarray(cv), _reference(cache_v, new_v.astype(jnp.bfloat16), pos))
