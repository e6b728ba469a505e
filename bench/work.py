"""Operations and bytes that prefill and decode need, from a configuration's
shapes and the positions that are actually live.

The counts are the algorithm's, never the compiled program's: a decode step
reads every weight once, the K/V of the positions each live slot holds, and
writes the new token's K/V; prefill counts the true prompt length, not the
padded bucket, and only the last position's logits.  So a share of the
roofline built on them measures the same work whatever implements it, and
reads at most 100% on a correct trace.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
NORM_BYTES = 4           # norm scales and biases are kept in float32


@dataclasses.dataclass(frozen=True)
class Shapes:
    """The sizes of a dense decoder that the counts depend on."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tie_embeddings: bool
    norm: str
    dtype_bytes: int

    @classmethod
    def from_model(cls, model: dict) -> "Shapes":
        """From the ``model`` section of a configuration file."""
        return cls(
            n_layers=model["n_layers"], d_model=model["d_model"],
            n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"],
            head_dim=model["head_dim"] or model["d_model"] // model["n_heads"],
            d_ff=model["d_ff"], vocab=model["vocab"],
            tie_embeddings=model["tie_embeddings"], norm=model["norm"],
            dtype_bytes=DTYPE_BYTES[model["dtype"]],
        )

    @property
    def layer_matrix_params(self) -> int:
        """Parameters of every layer's projections (all layers)."""
        d, h = self.d_model, self.head_dim
        attn = d * self.n_heads * h * 2 + d * self.n_kv_heads * h * 2
        return self.n_layers * (attn + 3 * d * self.d_ff)

    @property
    def norm_params(self) -> int:
        """Norm parameters: two per layer and the final one."""
        per = 2 * self.d_model if self.norm == "layernorm" else self.d_model
        return (2 * self.n_layers + 1) * per

    @property
    def head_params(self) -> int:
        """The LM head, over the true vocabulary."""
        return self.d_model * self.vocab

    @property
    def kv_bytes_per_position(self) -> int:
        """K and V of one position over all layers."""
        return 2 * self.n_layers * self.n_kv_heads * self.head_dim * self.dtype_bytes

    def param_bytes(self) -> int:
        """Bytes of every parameter as stored: matrices in the served type,
        norms in float32, the embedding table and, untied, the LM head."""
        mats = self.layer_matrix_params + self.head_params
        if not self.tie_embeddings:
            mats += self.head_params
        return mats * self.dtype_bytes + self.norm_params * NORM_BYTES

    def _weights_read(self, tokens: int) -> int:
        """Weights one forward pass reads: projections, norms, the LM head
        and the embedding rows of ``tokens`` input tokens."""
        mats = self.layer_matrix_params + self.head_params + tokens * self.d_model
        return mats * self.dtype_bytes + self.norm_params * NORM_BYTES

    def _attn_flops(self, queries_keys: int) -> int:
        """Scores and weighted values for ``queries_keys`` (query, key) pairs."""
        return 4 * self.n_layers * self.n_heads * self.head_dim * queries_keys

    def decode(self, positions: Iterable[int]) -> tuple[int, int]:
        """(FLOPs, bytes) of one decode step whose live slots hold
        ``positions`` cached positions each before the step."""
        positions = list(positions)
        n = len(positions)
        if not n:
            return 0, 0
        per_token = 2 * (self.layer_matrix_params + self.head_params)
        flops = n * per_token + self._attn_flops(sum(p + 1 for p in positions))
        kv = self.kv_bytes_per_position
        nbytes = self._weights_read(n) + sum(positions) * kv + n * kv
        return flops, nbytes

    def prefill(self, prompt_len: int) -> tuple[int, int]:
        """(FLOPs, bytes) of one prefill of ``prompt_len`` true tokens,
        ending in the last position's logits."""
        p = prompt_len
        flops = (2 * self.layer_matrix_params * p + 2 * self.head_params
                 + self._attn_flops(p * (p + 1) // 2))
        nbytes = self._weights_read(p) + p * self.kv_bytes_per_position
        return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peaks) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)


def roofline_pct(flops: float, nbytes: float, seconds: float, peaks) -> float:
    """Share of the roofline, in percent, of work done in ``seconds``."""
    return 100.0 * roofline_seconds(flops, nbytes, peaks) / seconds
