"""jit'd public wrapper for stream_pack.

``packed_branches(xs, ws)`` is the drop-in for "run these k independent
matmuls on k streams": stack, one kernel, unstack.  On TPU the Pallas path
is the real kernel; elsewhere pass ``interpret=True`` (tests, smoke) or
``use_kernel=False`` for the jnp oracle.  Asking for the compiled kernel
off TPU raises: it never quietly becomes the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import stream_pack_matmul
from .ref import stream_pack_matmul_ref


def _require_kernel_backend(interpret: bool) -> None:
    backend = jax.default_backend()
    if not interpret and backend != "tpu":
        raise ValueError(
            f"the compiled stream_pack kernel needs a TPU, not {backend!r}: "
            f"pass interpret=True or use_kernel=False"
        )


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def stream_pack(x, w, *, use_kernel: bool = True, interpret: bool = False):
    """x: (lanes, M, K), w: (lanes, K, N) → (lanes, M, N)."""
    if use_kernel:
        _require_kernel_backend(interpret)
        return stream_pack_matmul(x, w, interpret=interpret)
    return stream_pack_matmul_ref(x, w)


def packed_branches(xs, ws, **kw):
    """List-of-branches API: [(M,K)]*k, [(K,N)]*k → list of (M,N)."""
    x = jnp.stack(xs)
    w = jnp.stack(ws)
    out = stream_pack(x, w, **kw)
    return [out[i] for i in range(out.shape[0])]
