"""Operations and bytes of the program's kernels, from their block shapes.

``bsls_draw`` (the little step of the two-level exponential-mechanism draw,
``kernels/bsls_draw``) is one Pallas call per draw over a (G, M) float32
table: it DMAs the (8, M) tile holding the winning group, one (1, M) row of
Gumbel noise and the prefetched group id, adds the noise to one row, takes
its argmax and writes one int32.
"""
from __future__ import annotations

from bench.reference import group_shape

SUBLANES = 8
F32 = 4


def bsls_draw_cost(d: int, draw: dict) -> tuple:
    """(flops, bytes) of one little-step call for D coordinates, on the
    table that the configuration's ``draw`` states."""
    _, m = group_shape(d, draw)
    flops = 2 * m                        # noise add + argmax compare
    bytes_ = (SUBLANES * m + m) * F32 + F32 + F32   # tile, noise, g, out
    return float(flops), float(bytes_)


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peaks: dict) -> float:
    """Percent of its roofline that work which took ``seconds`` on the chip
    reached: the least time the chip could take (the larger of operations
    over peak FLOP/s and bytes over peak bytes/s) over the time it took.
    At the little step's shapes the bytes bound it, by four orders."""
    least = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
