"""Roofline arithmetic shared by every architecture.

An architecture module (``bench/arch/<arch>.py``) counts the operations
and bytes that prefill and decode need, from a configuration's shapes and
the positions that are actually live (its ``Shapes``).  The counts are
the algorithm's, never the compiled program's, so a share of the
roofline built on them measures the same work whatever implements it,
and reads at most 100% on a correct trace.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
NORM_BYTES = 4           # norm scales and biases are kept in float32


def roofline_seconds(flops: float, nbytes: float, peaks) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)


def roofline_pct(flops: float, nbytes: float, seconds: float, peaks) -> float:
    """Share of the roofline, in percent, of work done in ``seconds``."""
    return 100.0 * roofline_seconds(flops, nbytes, peaks) / seconds
