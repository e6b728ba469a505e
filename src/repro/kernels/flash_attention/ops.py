"""jit'd wrapper: model-layout flash attention.

Takes model-layout tensors (B, S, heads, head_dim), flattens to the kernel's
(B·heads, S, head_dim) layout, and runs the Pallas kernel (compiled on
TPU, interpreted with ``interpret=True``) or, with ``use_kernel=False``,
the oracle.  Asking for the compiled kernel off TPU raises: it never
quietly becomes the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention
from .ref import flash_attention_ref


def _require_kernel_backend(interpret: bool) -> None:
    backend = jax.default_backend()
    if not interpret and backend != "tpu":
        raise ValueError(
            f"the compiled flash-attention kernel needs a TPU, not "
            f"{backend!r}: pass interpret=True or use_kernel=False"
        )


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "causal", "window", "use_kernel", "interpret"),
)
def mha_flash(
    q,                        # (B, Sq, NH, hd)
    k,                        # (B, Skv, NKV, hd)
    v,
    *,
    scale=None,
    softcap: float = 0.0,
    causal: bool = True,
    window: int = 0,
    use_kernel: bool = True,
    interpret: bool = False,
):
    B, Sq, NH, hd = q.shape
    NKV = k.shape[2]
    group = NH // NKV
    qf = q.transpose(0, 2, 1, 3).reshape(B * NH, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * NKV, k.shape[1], hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * NKV, v.shape[1], hd)
    kw = dict(group=group, scale=scale, softcap=softcap, causal=causal, window=window)
    if use_kernel:
        _require_kernel_backend(interpret)
        out = flash_attention(qf, kf, vf, interpret=interpret, **kw)
    else:
        out = flash_attention_ref(qf, kf, vf, **kw)
    return out.reshape(B, NH, Sq, hd).transpose(0, 2, 1, 3)
