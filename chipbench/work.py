"""Operations and bytes that a computation needs, from its shapes.

These are the yardstick for rooflines and MFU: they count what the
algorithm needs, whatever implements it, so that a rewrite is judged on
the same numbers.

The aggregation ``out[d] = sum_{e: dst_e = d} coef_e * h[src_e]`` needs
one multiply-add per edge per feature.  Its bytes are each edge's source
row read once, the per-edge source id, destination id and coefficient
(4 bytes each), and each output row written once.  The backward pass,
where the step needs it, is the same aggregation transposed: each edge's
gradient row read once, the same per-edge words, and each source row's
gradient written once.
"""
from __future__ import annotations

EDGE_WORD_BYTES = 3 * 4      # source id, destination id, coefficient
FLOAT_BYTES = 4


def dense_flops(m: int, k: int, n: int) -> float:
    """One (m, k) @ (k, n) product."""
    return 2.0 * m * k * n


def aggregation_flops(n_edges: int, width: int, *, backward: bool) -> float:
    return 2.0 * n_edges * width * (2 if backward else 1)


def aggregation_bytes(n_edges: int, width: int, n_src: int, n_dst: int, *,
                      backward: bool, itemsize: int = FLOAT_BYTES) -> float:
    fwd = (n_edges * width * itemsize + n_edges * EDGE_WORD_BYTES
           + n_dst * width * itemsize)
    bwd = (n_edges * width * itemsize + n_edges * EDGE_WORD_BYTES
           + n_src * width * itemsize)
    return float(fwd + (bwd if backward else 0))


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple:
    """``(share in %, bound)``: the least time the chip could take, the
    larger of operations over peak FLOP/s and bytes over peak bytes/s,
    divided by the time measured.  ``bound`` names the larger term."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
