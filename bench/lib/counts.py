"""Operations and bytes a kernel's work needs, from algorithmic counts.

The counts follow the algorithm, not an implementation: one distance
computation (NDC, the paper's cost unit) reads one stored vector and the
row's filter attributes, and each expansion reads one neighbour list.
Wider rows that a kernel DMAs for alignment are not counted, so a share
computed from these counts reads the same work whatever implements it.
"""
from __future__ import annotations

BYTES_PER_STORED_ELEMENT = {"float32": 4, "int8": 1}


def traverse_bytes(ndc: int, expansions: int, dim: int, precision: str,
                   label_words: int, value_attrs: int, degree: int) -> int:
    """Bytes a filtered traversal reads: per distance computation the
    stored vector and the filter attributes (label words and numeric
    channels, 4 bytes each); per expansion its neighbour list."""
    per_ndc = dim * BYTES_PER_STORED_ELEMENT[precision] + 4 * (
        label_words + value_attrs)
    return ndc * per_ndc + expansions * degree * 4


def traverse_ops(ndc: int, dim: int) -> int:
    """Arithmetic of the distance computations: a multiply and an add per
    dimension (the norm terms are per row and per query)."""
    return 2 * ndc * dim


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak_ops: float, peak_bytes_per_s: float):
    """(share of the roofline in %, which bound) for work done in
    `seconds` of kernel time; None where no kernel time was read."""
    if seconds <= 0:
        return None
    t_ops = ops / peak_ops
    t_bytes = nbytes / peak_bytes_per_s
    bound = "bytes" if t_bytes >= t_ops else "ops"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
